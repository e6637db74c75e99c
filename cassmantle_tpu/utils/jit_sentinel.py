"""JitCompileSentinel: the runtime half of the recompile defense.

The static pass (``cassmantle_tpu/analysis/recompile.py``) proves what
it can see — jit built in loops, per-call statics, mutable captures.
This sentinel covers the rest at runtime: it counts **actual XLA
compilations per jitted function**, so a recompile regression on a
steady-state serving path (a bucket key that quietly became per-call,
a shape that stopped being bucketed) fails a tier-1 test instead of
shipping as a silent 100x latency cliff — the same static-pass +
runtime-sentinel pairing as ``lockorder.py`` / ``utils/locks.py``.

How it listens: ``jax.monitoring`` fires a ``backend_compile`` event
per compile but carries **no function name**, so the sentinel instead
attaches a counting ``logging.Filter`` to jax's compile log
(``jax._src.interpreters.pxla`` emits one DEBUG record
``"Compiling <name> with global shapes and types ..."`` per cache-miss
compilation) and parses the name out — passing through, unchanged,
every record the operator's own logging config would have emitted.

Compile **wall time** (ISSUE 14) rides the same mechanism: jax wraps
every backend compile in ``dispatch.log_elapsed_time``, which emits
``"Finished XLA compilation of jit(<name>) in <secs> sec"`` on the
``jax._src.dispatch`` logger. A second filter parses name + seconds
into the ``jit.compile_s`` histogram (per-function ``fn=`` label), the
cumulative ``jit.compile_seconds`` counter (bench entries attach its
per-run delta — a 100 s SDXL recompile is *visible* in the trajectory,
not just countable), a per-name total (:func:`compile_time_snapshot`,
surfaced in the `/readyz` ``device_telemetry`` block), and — for
compiles ≥ 1 s, the same threshold the persistent cache uses — a
flight-recorder event (`/debugz` kind ``jit.compile``). Sub-second
compiles stay metric-only so warmup bursts cannot flush the event ring
of the supervision story an operator is actually triaging.

Known limit: the log line carries only the function's bare
``__name__``, so two distinct jitted functions sharing a name (e.g. a
jitted ``apply`` on two models) share one counter — the second
function's warmup compile registers as a "recompile" of the first.
Keep jitted entry-point names distinct where it matters, scope test
assertions with ``no_new_compiles(only=...)``, and read production
``jit.recompiles`` as a steady-state RATE signal, not per-event truth
(the per-name `/debugz` events say which name to go look at). That logger is jax's stable
compile-path narration; if a future jax renames it the sentinel
degrades to counting nothing — tests that assert a *seeded* recompile
raises (tests/test_check_jax.py) exist precisely to catch that
silently-disarmed state.

Usage (tests — an autouse conftest fixture arms + resets per test):

    warmup()                          # compile everything once
    with jit_sentinel.no_new_compiles():
        steady_state_traffic()        # raises JitRecompileError on ANY
                                      # new compilation, with names

Production: ``CASSMANTLE_JIT_SENTINEL=1`` arms log-only counting when
the pipelines boot (``enable_compile_cache`` arms it): every compile
counts ``jit.compiles``; a repeat compile of an already-compiled
function name counts ``jit.recompiles`` and lands in the flight
recorder (``/debugz`` kind ``jit.recompile``). Bucketed paths
legitimately re-compile once per bucket during warmup — the alert
signal is ``jit.recompiles`` *still climbing in steady state*, not its
absolute value (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import logging
import os
import threading
from contextlib import contextmanager
from typing import Dict, Iterable, Optional

from cassmantle_tpu.utils.logging import get_logger, metrics

log = get_logger("jit_sentinel")

#: jax's compile-path narration logger; one record per actual compile
_COMPILE_LOGGER = "jax._src.interpreters.pxla"
_PREFIX = "Compiling "
#: the elapsed-time record (dispatch.log_elapsed_time) — fires once per
#: backend compile with the wall seconds baked into the message
_FINISHED_LOGGER = "jax._src.dispatch"
_FINISHED_PREFIX = "Finished XLA compilation of "
#: flight-recorder threshold: compiles at/over this land in /debugz
#: (kind jit.compile); matches jax_persistent_cache_min_compile_time
_RECORDER_MIN_S = 1.0


class JitRecompileError(RuntimeError):
    """A post-warmup compilation happened inside a no_new_compiles
    window (the recompile the bucket discipline exists to prevent)."""


_lock = threading.Lock()
_counts: Dict[str, int] = {}
_compile_s: Dict[str, float] = {}
# (logger name, attached filter, pre-sentinel level) per listened logger
_filters: list = []


def _record_compile(name: str) -> None:
    name = _normalize_fn_name(name)
    with _lock:
        n = _counts.get(name, 0) + 1
        _counts[name] = n
    metrics.inc("jit.compiles")
    if n > 1:
        metrics.inc("jit.recompiles")
        # lazy import: utils never depends on obs at module scope (the
        # circuit-breaker rule, same as locks.py)
        from cassmantle_tpu.obs.recorder import flight_recorder

        flight_recorder.record("jit.recompile", fn=name, count=n)
        log.info("jit recompile #%d of %r", n, name)


def _normalize_fn_name(name: str) -> str:
    """Both records name the function as ``jit(<name>)`` (jax 0.9.0;
    the Compiling record used the bare ``<name>`` before): strip the
    wrapper so counts, times and ``no_new_compiles(only=...)`` all key
    the function's own ``__name__``."""
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _record_compile_time(name: str, seconds: float) -> None:
    name = _normalize_fn_name(name)
    with _lock:
        _compile_s[name] = _compile_s.get(name, 0.0) + seconds
    metrics.observe("jit.compile_s", seconds, labels={"fn": name})
    metrics.inc("jit.compile_seconds", seconds)
    if seconds >= _RECORDER_MIN_S:
        from cassmantle_tpu.obs.recorder import flight_recorder

        flight_recorder.record("jit.compile", fn=name,
                               seconds=round(seconds, 3))
        log.info("jit compile of %r took %.1fs", name, seconds)


def _parse_compiling(msg: str) -> None:
    if msg.startswith(_PREFIX):
        _record_compile(msg[len(_PREFIX):].split(" ", 1)[0])


def _parse_finished(msg: str) -> None:
    if not msg.startswith(_FINISHED_PREFIX):
        return
    # "Finished XLA compilation of jit(fn) in 1.234 sec"
    body = msg[len(_FINISHED_PREFIX):]
    name, _, tail = body.rpartition(" in ")
    if not name:
        return
    try:
        seconds = float(tail.split()[0])
    except (ValueError, IndexError):
        return
    _record_compile_time(name, seconds)


class _CompileLogFilter(logging.Filter):
    """Feeds ``handle(message)`` from a logger-level filter (filters
    run before handlers AND propagation, so nothing needs to be
    attached downstream). The filter also keeps the sentinel's
    forced-DEBUG level from changing what operators see: records the
    PRE-sentinel effective level would have emitted pass through
    untouched (warnings/errors keep flowing — and if the operator
    configured DEBUG themselves, the compile narration still prints);
    only the records our level-forcing newly enabled are swallowed.
    Counting must never raise — a sentinel that can break compilation
    is worse than no sentinel."""

    def __init__(self, prior_effective: int, handle) -> None:
        super().__init__()
        self.prior_effective = prior_effective
        self._handle = handle

    def filter(self, record: logging.LogRecord) -> bool:
        try:
            self._handle(record.getMessage())
        except Exception:  # pragma: no cover - defensive
            pass
        return record.levelno >= self.prior_effective


#: (logger name, message handler) — the two compile-narration feeds
_LISTENERS = (
    (_COMPILE_LOGGER, _parse_compiling),
    (_FINISHED_LOGGER, _parse_finished),
)


def enable_sentinel() -> None:
    """Attach the compile-log listeners (idempotent): compile COUNTS
    from pxla's Compiling records, compile WALL TIME from dispatch's
    Finished records. Forces both loggers to DEBUG so the per-compile
    records actually fire; previous levels are restored by
    :func:`disable_sentinel`."""
    if _filters:
        return
    for logger_name, handle in _LISTENERS:
        logger = logging.getLogger(logger_name)
        filt = _CompileLogFilter(logger.getEffectiveLevel(), handle)
        _filters.append((logger_name, filt, logger.level))
        logger.addFilter(filt)
        logger.setLevel(logging.DEBUG)


def disable_sentinel() -> None:
    global _filters
    for logger_name, filt, prior_level in _filters:
        logger = logging.getLogger(logger_name)
        logger.removeFilter(filt)
        logger.setLevel(prior_level)
    _filters = []


def sentinel_active() -> bool:
    return bool(_filters)


def maybe_enable_from_env() -> None:
    """Production arming: CASSMANTLE_JIT_SENTINEL=1 turns on log-only
    compile counting. Called from ``enable_compile_cache`` so every
    pipeline/scorer boot arms it without its own wiring."""
    if os.environ.get("CASSMANTLE_JIT_SENTINEL", "") not in ("", "0"):
        enable_sentinel()


def reset_counts() -> None:
    with _lock:
        _counts.clear()
        _compile_s.clear()


def snapshot() -> Dict[str, int]:
    """Compile counts per jitted-function name since the last reset."""
    with _lock:
        return dict(_counts)


def compile_time_snapshot() -> Dict[str, float]:
    """Cumulative compile wall seconds per function since the last
    reset — the `/readyz` device_telemetry block's compile summary."""
    with _lock:
        return dict(_compile_s)


def compiles(name: Optional[str] = None) -> int:
    with _lock:
        if name is not None:
            return _counts.get(name, 0)
        return sum(_counts.values())


@contextmanager
def no_new_compiles(only: Optional[Iterable[str]] = None,
                    allow: Iterable[str] = ()):
    """Assert zero compilations happen inside the block — the
    "steady state after warmup" contract of every bucketed serving
    path. Raises :class:`JitRecompileError` naming each function that
    compiled and how many times.

    ``only`` restricts the assertion to specific jitted-function names
    (default: ANY compilation fails — the strongest form; jax-internal
    helper jits are cached by shape too, so steady-state traffic in
    warmed buckets compiles nothing at all). ``allow`` exempts names
    expected to compile (e.g. a bucket deliberately entered cold).

    No-op (with a warning) when the sentinel is not armed — the autouse
    test fixture arms it, so in-tree tests never hit that path."""
    if not sentinel_active():
        log.warning("no_new_compiles: sentinel not armed; assertion "
                    "is vacuous")
        yield
        return
    before = snapshot()
    yield
    after = snapshot()
    allow = set(allow)
    new = {k: n - before.get(k, 0) for k, n in after.items()
           if n > before.get(k, 0) and k not in allow}
    if only is not None:
        keep = set(only)
        new = {k: n for k, n in new.items() if k in keep}
    if new:
        detail = ", ".join(f"{k} x{n}" for k, n in sorted(new.items()))
        raise JitRecompileError(
            f"post-warmup compilation(s) inside a no_new_compiles "
            f"window: {detail} — a steady-state serving path "
            f"recompiled (bucket key regressed?)")
