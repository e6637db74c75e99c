"""Device-loss detection and serving-state rebuild (ISSUE 17, rung 3).

A TPU runtime can die under a live server — preempted VM, wedged PCIe
link, driver crash. jax surfaces that as ``XlaRuntimeError`` (or a
transport error wrapping one) on the NEXT dispatch, and every buffer the
process holds (params, staged-slot tensors, compiled-executable device
state) is garbage from that point on. Without handling, each request
thereafter burns a full dispatch timeout before failing, and nothing
ever repairs the process short of a restart.

This module closes the loop:

- :func:`classify_device_loss` decides whether an exception from a
  dispatch region (or a DeviceHealth probe) means the *runtime* is gone,
  as opposed to a data-dependent failure (OutputInvalid), a deadline, or
  a wedge (the watchdog's department).
- :class:`DeviceRecoveryManager` owns the single-flight recovery: flip
  the supervisor into ``device_lost`` (queues fail fast, `/readyz`
  serves 503 naming the state), then rebuild serving state on a
  background thread — re-upload checkpoints through the
  fingerprint-verified load path (utils/checkpoint.py) and re-warm the
  hot dispatch paths under a ``no_new_compiles`` window. Bounded
  retries with backoff ride a token-bucket :class:`~cassmantle_tpu.
  utils.retry.RetryBudget`; exhaustion is PERMANENT loss — the worker
  stays ``device_lost`` (the LB drains on the 503, docs/DEPLOY.md §7b)
  and the optional ``on_permanent`` hook fires.

Kill switch (docs/DEPLOY.md §6): ``CASSMANTLE_NO_DEVICE_RECOVERY``
disables the REBUILD only — a classified loss still flips the
supervisor (fail-fast + 503 beat timing out every request), it just
stays there for the operator. Read per-call so flipping the env var
needs no restart.

Chaos: the ``device.lost`` fault point (serving dispatch regions)
raises ``ChaosInjected`` with the fault name in its message, which
classifies exactly like a real loss — the ``device_loss_drill`` bench
entry drives this whole path end to end.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional

from cassmantle_tpu.obs.recorder import flight_recorder
from cassmantle_tpu.utils.logging import get_logger, metrics
from cassmantle_tpu.utils.retry import RetryBudget

log = get_logger("device_recovery")

# Exception type names (matched anywhere in the cause/context chain)
# that mean the accelerator runtime itself failed. Name-matched, not
# isinstance: jaxlib's XlaRuntimeError moves modules across versions,
# and tests raise look-alikes without a dead TPU to hand.
_LOSS_TYPES = frozenset({"XlaRuntimeError", "DeadBufferError"})

# Message substrings (lowercased) that mark runtime loss even under a
# generic exception type. "device.lost" is the chaos fault-point name —
# ChaosInjected carries it, so drills classify like real losses.
_LOSS_MARKERS = (
    "device.lost",
    "device is lost",
    "device lost",
    "runtime is gone",
    "data transfer failed",
    "failed to enqueue",
    "hardware failure",
    "tpu driver",
)


def recovery_disabled() -> bool:
    """CASSMANTLE_NO_DEVICE_RECOVERY kill switch, read per-call."""
    return os.environ.get(
        "CASSMANTLE_NO_DEVICE_RECOVERY", ""
    ).lower() not in ("", "0", "false", "no", "off")


def classify_device_loss(exc: BaseException) -> Optional[str]:
    """A short reason string when ``exc`` (or anything in its
    cause/context chain) looks like accelerator-runtime loss, else
    None. Deliberately conservative: deadlines, backpressure, and
    invalid-output failures are NOT losses — misclassifying those
    would bounce serving through a needless rebuild."""
    seen = set()
    node: Optional[BaseException] = exc
    while node is not None and id(node) not in seen:
        seen.add(id(node))
        name = type(node).__name__
        if name in _LOSS_TYPES:
            return f"{name}: {str(node)[:120]}"
        text = str(node).lower()
        for marker in _LOSS_MARKERS:
            if marker in text:
                return f"{name}: {marker}"
        node = node.__cause__ or node.__context__
    return None


class DeviceRecoveryManager:
    """Single-flight device-loss recovery.

    ``rebuild`` performs ONE rebuild attempt (re-upload params; raises
    on failure); ``warm`` optionally re-drives the hot paths after a
    successful rebuild (a failure there fails the attempt — a rebuilt
    device that cannot serve is not recovered). Both run on the
    manager's daemon thread, never on a dispatch thread.
    """

    def __init__(
        self,
        *,
        supervisor,
        rebuild: Callable[[], None],
        warm: Optional[Callable[[], None]] = None,
        on_permanent: Optional[Callable[[str], None]] = None,
        max_attempts: int = 3,
        backoff_s: float = 2.0,
        budget: Optional[RetryBudget] = None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ) -> None:
        self.supervisor = supervisor
        self.rebuild = rebuild
        self.warm = warm
        # wired by the server layer when a fabric is serving (begin the
        # PR 12 drain); default None leaves the worker device_lost —
        # /readyz 503 IS the drain signal for the LB
        self.on_permanent = on_permanent
        self.max_attempts = max_attempts
        self.backoff_s = backoff_s
        # rebuilds re-upload multi-GB checkpoints: a flapping device
        # must not melt the host re-reading them in a tight loop. ~6
        # attempts burst, one earned back per minute.
        self.budget = budget or RetryBudget(
            "device_recovery", capacity=6.0, refill_per_s=1.0 / 60.0,
            clock=clock)
        self.clock = clock
        self.sleep = sleep
        self._lock = threading.Lock()
        self._recovering = False
        self._thread: Optional[threading.Thread] = None
        self.permanent = False

    # -- classification entry points --------------------------------------
    def note_dispatch_exception(self, exc: BaseException) -> bool:
        """Called from dispatch error paths (BatchingQueue
        ``on_dispatch_error``, the service's generate/similarity arms).
        Returns True when ``exc`` classified as device loss (recovery
        has been kicked off or is already in flight)."""
        reason = classify_device_loss(exc)
        if reason is None:
            return False
        self.begin_recovery(reason)
        return True

    # DeviceHealth probe raises funnel through the same classifier; a
    # probe that RAISES (vs times out) carries the runtime's own error
    note_probe_exception = note_dispatch_exception

    # -- recovery ----------------------------------------------------------
    def begin_recovery(self, reason: str) -> None:
        """Flip the supervisor and start the single-flight rebuild
        thread. Re-entrant: concurrent classifications during an active
        recovery (every queue fails fast with the same root cause)
        coalesce into the one in-flight attempt."""
        with self._lock:
            if self._recovering or self.permanent:
                return
            self._recovering = True
        self.supervisor.note_device_lost(reason)
        if recovery_disabled():
            log.error(
                "device recovery disabled (CASSMANTLE_NO_DEVICE_RECOVERY);"
                " worker stays device_lost: %s", reason)
            with self._lock:
                self._recovering = False
            return
        thread = threading.Thread(
            target=self._recover, args=(reason,), daemon=True,
            name="device-recovery")
        with self._lock:
            self._thread = thread
        thread.start()

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for an in-flight recovery thread (tests, drills)."""
        thread = self._thread
        if thread is not None:
            thread.join(timeout)

    @property
    def recovering(self) -> bool:
        with self._lock:
            return self._recovering

    def _recover(self, reason: str) -> None:
        start = self.clock()
        try:
            for attempt in range(1, self.max_attempts + 1):
                if not self.budget.acquire():
                    log.error("device recovery: retry budget exhausted "
                              "after %d attempt(s)", attempt - 1)
                    break
                try:
                    self.rebuild()
                    if self.warm is not None:
                        self.warm()
                except Exception as exc:
                    log.exception("device recovery attempt %d/%d failed",
                                  attempt, self.max_attempts)
                    flight_recorder.record(
                        "device.recovery_failed", attempt=attempt,
                        error=f"{type(exc).__name__}: {str(exc)[:160]}")
                    if attempt < self.max_attempts:
                        self.sleep(self.backoff_s * attempt)
                    continue
                elapsed = self.clock() - start
                metrics.inc("device.recoveries")
                metrics.observe("device.recovery_s", elapsed)
                self.supervisor.note_device_recovered()
                log.warning("device recovered in %.2fs (attempt %d/%d)",
                            elapsed, attempt, self.max_attempts)
                return
            # attempts (or budget) exhausted: permanent loss. The worker
            # stays device_lost — queues fail fast, /readyz serves 503
            # until the operator replaces it (docs/DEPLOY.md §7b).
            self.permanent = True
            metrics.inc("device.recovery_permanent")
            flight_recorder.record("device.recovery_permanent",
                                   reason=reason)
            log.critical(
                "device recovery FAILED permanently (%s); worker stays "
                "device_lost — drain and replace it", reason)
            if self.on_permanent is not None:
                try:
                    self.on_permanent(reason)
                # lint: ignore[swallowed-error] — advisory drain hook: the permanent-loss event itself is counted and flight-recorded just above
                except Exception:
                    log.exception("permanent-loss drain hook failed")
        finally:
            with self._lock:
                self._recovering = False
