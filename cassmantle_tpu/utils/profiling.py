"""Host regions and device-synchronized stage timing, on one clock.

The reference has no tracing at all (SURVEY.md §5.1). A region of host
code that matters to a request is written to three records at once: a
histogram in ``metrics``, a span in the request's trace (obs/trace.py,
when one is ambient) and a ``jax.profiler.TraceAnnotation`` of the same
name, so that a profiler session (``POST /debug/trace``) shows the
program's own spans on the host lines beside the device's operations.
``block_timer`` additionally synchronizes on device results, so its
timings measure device work, not dispatch.

Device time is named from INSIDE the compiled programs by
``jax.named_scope`` (stage scopes such as ``denoise_step``; Flax adds a
scope per module call) and by ``name=`` on the Pallas calls: both are
op metadata, which a device trace carries on every event. A host
annotation opened inside a jit-traced function fires once, at trace
time, and leaves nothing in the program — never open one there.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import time
from typing import Callable, Iterator, Optional

import jax

from cassmantle_tpu.utils.logging import metrics


def host_region(name: str):
    """Name a region of HOST code in the profiler's trace. The one door
    to ``TraceAnnotation``: ``Tracer.span``, ``host_span``,
    ``block_timer`` and the collector's ``host.gc`` come through here
    (with no profiler session active a TraceMe is a flag test)."""
    return jax.profiler.TraceAnnotation(name)


#: the collection in progress (the collector runs one at a time) and the
#: seconds of the collections whose histogram observation is still due
_gc_open: list = []
_gc_unobserved: list = []


def _gc_region(phase: str, _info: dict) -> None:
    """A ``gc.callbacks`` entry: each collection of Python's cyclic
    collector is the host region ``host.gc`` on the collecting thread and
    an observation of ``host.gc_s``. A collection starts wherever an
    allocation tips the collector's count, inside the metrics registry's
    locked sections too, so the observation does not wait for that lock:
    a collection that finds it taken is observed with the next one."""
    if phase == "start":
        region = host_region("host.gc")
        region.__enter__()
        _gc_open.append((region, time.perf_counter()))
    elif _gc_open:
        region, start = _gc_open.pop()
        _gc_unobserved.append(time.perf_counter() - start)
        region.__exit__(None, None, None)
        if metrics.observe_nowait("host.gc_s", _gc_unobserved):
            _gc_unobserved.clear()


def install_gc_region() -> None:
    """Time every collection of the cyclic collector as ``host.gc``;
    once a process, however often called. Between collections it costs
    nothing."""
    if _gc_region not in gc.callbacks:
        gc.callbacks.append(_gc_region)


def named_jit(fn: Callable, name: str, **jit_kwargs):
    """``jax.jit(fn)`` under a fixed program name: the trace's ``XLA
    Modules`` line and every ``op_name`` (``jit(<name>)/...``) carry it,
    whatever function object reached the jit."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)


def _record_ambient_span(name: str, start_wall: float, duration_s: float,
                         attrs: dict) -> None:
    """A finished region as a child span of the ambient trace; nothing
    without one (a bare call mints no orphan root trace)."""
    from cassmantle_tpu.obs.trace import current_ctx, tracer

    ctx = current_ctx()
    if ctx is not None and ctx.sampled:
        tracer.record_span(
            name, tracer.child_ctx(ctx), parent_id=ctx.span_id,
            start_wall=start_wall, duration_s=duration_s, attrs=attrs)


@contextlib.contextmanager
def host_span(name: str) -> Iterator[None]:
    """Time a host region (a wait, a copy, a check) to histogram
    ``<name>_s``, span ``<name>`` and the profiler annotation
    ``<name>``."""
    start_wall = time.time()
    start = time.perf_counter()
    try:
        with host_region(name):
            yield
    finally:
        elapsed = time.perf_counter() - start
        # tools/check_metrics.py lints the literal at the call site
        # (host_span("a.b") emits the histogram a.b_s)
        metrics.observe(name + "_s", elapsed)
        _record_ambient_span(name, start_wall, elapsed, {})


@contextlib.contextmanager
def block_timer(name: str, *results, flops_est=None,
                pipeline: Optional[str] = None,
                attrs: Optional[dict] = None) -> Iterator[list]:
    """Time a region to metrics, blocking on listed device arrays at exit.

    Also records a **device-synchronized stage span** into the active
    trace (obs/trace.py) when one is ambient, carrying ``attrs``, and
    opens the profiler annotation ``name`` around the region: the
    block-until-ready at exit means both cover the device work, not
    just dispatch — these are the per-stage spans a request trace shows
    for scorer encodes, prompt decodes, and image generations. On the
    host clock a stage that was dispatched behind another program
    includes its time in the device's queue.

    Roofline attribution (ISSUE 14): callers that know their dispatch's
    analytic FLOPs (obs/costmodel.py) pass ``flops_est`` (a float, or a
    zero-arg callable evaluated at exit for costs only known after
    dispatch — the prompt path's bucket grouping) plus a ``pipeline``
    label. The span then carries ``flops_est``/``mxu_utilization``
    attrs, ``request.device_flops`` accumulates the attributed FLOPs,
    and, on a TPU, ``pipeline.mxu_utilization{pipeline=}`` reports
    achieved-vs-peak (flops / device-synchronized seconds / the peak
    ``costmodel.chip_peak_flops`` holds for this ``device_kind``; no
    gauge off-TPU, an error for a TPU kind with no peak on record).
    ``pipeline`` alone also marks a dispatch boundary for the HBM
    highwater tracker (obs/device.py)."""
    sink: list = []
    start_wall = time.time()
    start = time.perf_counter()
    ok = False
    try:
        with host_region(name):
            try:
                yield sink
                ok = True
            finally:
                for r in list(results) + sink:
                    jax.block_until_ready(r)
    finally:
        elapsed = time.perf_counter() - start
        metrics.observe(name, elapsed)
        attrs = dict(attrs or {}, device_synced=True)
        flops = None
        # attribution only for dispatches that COMPLETED: a body that
        # raised (OOM, chaos injection) did not do its analytic FLOPs,
        # and dividing them by the short elapsed-at-failure would spike
        # mxu_utilization above 1.0 exactly while an operator triages
        if ok and flops_est is not None:
            try:
                flops = float(flops_est() if callable(flops_est)
                              else flops_est)
            except Exception:  # attribution must never fail a dispatch
                flops = None
        if flops is not None and flops > 0:
            from cassmantle_tpu.obs.costmodel import chip_peak_flops

            labels = {"pipeline": pipeline} if pipeline else None
            metrics.inc("request.device_flops", flops, labels=labels)
            attrs["flops_est"] = flops
            peak = chip_peak_flops()
            if elapsed > 0 and peak is not None:
                mxu = flops / elapsed / peak
                attrs["mxu_utilization"] = round(mxu, 6)
                metrics.gauge("pipeline.mxu_utilization", mxu,
                              labels=labels)
        if pipeline:
            # HBM highwater at the dispatch boundary: the sync above
            # means this pipeline's buffers are still resident
            from cassmantle_tpu.obs.device import note_dispatch

            note_dispatch(pipeline)
        _record_ambient_span(name, start_wall, elapsed, attrs)
