"""Device liveness / health checks (SURVEY.md §5.3).

The reference's failure handling is per-call retry + skip-don't-crash
(utils.py:43-61, backend.py:123-129); it has no health surface at all.
Here the serving layer gets one: a tiny jitted probe computation runs on
the default device with a wall-clock deadline (a wedged or dying chip
makes device calls hang rather than raise — exactly the failure this
detects), and the result is cached briefly so `/healthz`
polling can't pile probes onto the device.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from cassmantle_tpu.utils.locks import OrderedLock
from cassmantle_tpu.utils.logging import get_logger, metrics

log = get_logger("health")


_probe_jit = None


def _probe_once() -> bool:
    import jax
    import jax.numpy as jnp

    # One process-wide jitted probe: a fresh lambda per call would miss
    # the jit cache (identity-keyed) and re-trace/compile every probe.
    global _probe_jit
    if _probe_jit is None:
        _probe_jit = jax.jit(lambda v: (v * 2.0).sum())
    x = jnp.arange(8, dtype=jnp.float32)
    y = _probe_jit(x)
    return float(jax.block_until_ready(y)) == 56.0


class _Probe:
    """One probe on a DAEMON thread: a stuck XLA call can't be cancelled,
    only disowned — daemon threads never pin process exit."""

    def __init__(self) -> None:
        self.done = threading.Event()
        self.ok = False
        # the exception when the probe RAISED (vs hung/miscomputed):
        # a raise carries the runtime's own error, which the recovery
        # manager can classify as device loss (a timeout cannot — a
        # wedge is the watchdog's department)
        self.exc: Optional[BaseException] = None
        self.started_at = time.monotonic()
        threading.Thread(
            target=self._run, daemon=True, name="device-probe"
        ).start()

    def _run(self) -> None:
        try:
            self.ok = bool(_probe_once())
        except Exception as exc:
            log.warning("device probe failed: %s", exc)
            self.ok = False
            self.exc = exc
        self.done.set()


class DeviceHealth:
    """Cached device-liveness prober.

    ``check()`` returns (healthy, age_s). A probe that exceeds
    ``timeout_s`` marks the device unhealthy WITHOUT blocking the caller
    beyond the timeout; the hung probe thread is left behind (daemon)
    and reused if it ever completes.
    """

    def __init__(self, timeout_s: float = 10.0, cache_s: float = 15.0):
        self.timeout_s = timeout_s
        self.cache_s = cache_s
        # leaf tier of the docs/STATIC_ANALYSIS.md lock hierarchy: the
        # probe cache nests inside anything, holds nothing else
        self._lock = OrderedLock("health.device", rank=50)
        self._healthy: Optional[bool] = None
        self._checked_at = 0.0
        self._inflight: Optional[_Probe] = None
        # failure CLASS behind a cached False verdict: "timeout" (the
        # probe hung — a wedge, the watchdog's department), or
        # "raise:<ExcType>" (the runtime itself errored — candidate
        # device loss). None while healthy/unknown.
        self._failure: Optional[str] = None
        # wired by the serving layer (DeviceRecoveryManager
        # .note_probe_exception): called OUTSIDE the lock with the
        # probe's exception when a probe completes by raising, so a
        # dispatch-quiet worker still detects runtime loss
        self.on_probe_error = None  # type: Optional[callable]

    def last_verdict(self):
        """The cached verdict (True/False/None-unknown) with NO probe
        dial — the request-path read (scorer hedging) where blocking up
        to ``timeout_s`` on a wedged device is not an option."""
        with self._lock:
            return self._healthy

    def last_failure(self) -> Optional[str]:
        """Failure class behind the cached verdict ("timeout" /
        "raise:<ExcType>"), None while healthy or unknown. Surfaced so
        a /readyz reader (and the recovery manager) can tell a wedged
        device from a dead runtime."""
        with self._lock:
            return self._failure

    def invalidate(self) -> None:
        """Drop the cached verdict (device-loss recovery: a freshly
        rebuilt runtime must be re-probed, not vouched for by the dead
        one's verdict)."""
        with self._lock:
            self._healthy = None
            self._failure = None
            self._checked_at = 0.0

    def check(self) -> tuple:
        with self._lock:
            age = time.monotonic() - self._checked_at
            if self._healthy is not None and age < self.cache_s:
                return self._healthy, age
            stale = (
                self._inflight is not None
                and not self._inflight.done.is_set()
                and time.monotonic() - self._inflight.started_at
                > 2 * self.timeout_s
            )
            if self._inflight is None or stale:
                # a probe hung past its deadline is disowned (daemon
                # thread) and replaced, so a device that RECOVERS is
                # re-detected instead of being pinned unhealthy forever
                self._inflight = _Probe()
            probe = self._inflight
        if probe.done.wait(timeout=self.timeout_s):
            ok = probe.ok
            failure = (None if ok else
                       f"raise:{type(probe.exc).__name__}"
                       if probe.exc is not None else "miscompute")
        else:
            ok = False
            failure = "timeout"
            log.warning("device probe exceeded %.1fs (device hung?)",
                        self.timeout_s)
        with self._lock:
            if probe.done.is_set():
                self._inflight = None
            self._healthy = ok
            self._failure = failure
            self._checked_at = time.monotonic()
        metrics.gauge("health.device_ok", 1.0 if ok else 0.0)
        hook = self.on_probe_error
        if probe.exc is not None and hook is not None:
            # outside the lock: the hook may start a recovery thread
            # that flips supervisor state
            try:
                hook(probe.exc)
            except Exception:
                log.exception("probe-error hook failed")
        return ok, 0.0
