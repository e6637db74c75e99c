"""JAX profiler helpers: trace capture + per-stage device timing.

The reference has no tracing at all (SURVEY.md §5.1). We wrap
``jax.profiler`` so any serving stage can be captured to a TensorBoard trace
directory, and provide a ``block_timer`` that synchronizes on device results
so timings measure device work, not dispatch.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax

from cassmantle_tpu.utils.logging import get_logger, metrics

log = get_logger("profiling")


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace if log_dir is set; no-op otherwise."""
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield
    log.info("profiler trace written to %s", log_dir)


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Name a region in the device trace (shows up in TensorBoard)."""
    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def block_timer(name: str, *results, flops_est=None,
                pipeline: Optional[str] = None) -> Iterator[list]:
    """Time a region to metrics, blocking on listed device arrays at exit.

    Also records a **device-synchronized stage span** into the active
    trace (obs/trace.py) when one is ambient: the block-until-ready at
    exit means the span's duration covers the device work, not just
    dispatch — these are the per-stage spans a request trace shows for
    scorer encodes, prompt decodes, and image generations.

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
    from cassmantle_tpu.obs.trace import current_ctx, tracer

    sink: list = []
    start_wall = time.time()
    start = time.perf_counter()
    ok = False
    try:
        yield sink
        ok = True
    finally:
        for r in list(results) + sink:
            jax.block_until_ready(r)
        elapsed = time.perf_counter() - start
        metrics.observe(name, elapsed)
        attrs = {"device_synced": True}
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
        ctx = current_ctx()
        if ctx is not None and ctx.sampled:
            tracer.record_span(
                name, tracer.child_ctx(ctx), parent_id=ctx.span_id,
                start_wall=start_wall, duration_s=elapsed,
                attrs=attrs)
