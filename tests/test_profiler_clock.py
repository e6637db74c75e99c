"""One clock (ISSUE 26): a span's ``start_ns`` is on the clock the
profiler stamps host events with, and every context-managed region is in
the profiler's trace under its own name.

Which clock that is was looked up, not assumed: a TraceMe reads the
realtime clock, and an ``.xplane.pb`` holds each event's offset from the
session's ``profile_start_time`` (a statistic of the ``Task Environment``
plane, on the same clock). So ``start_ns - profile_start_time`` of a span
the ring keeps has to be the start of the annotation of the same name,
which is what lets a span measured after the fact (``prompt.queue_wait``)
be laid over a device trace.
"""

import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from cassmantle_tpu.obs.trace import Tracer
from cassmantle_tpu.utils.profiling import block_timer, host_span

MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """One CPU profiler session (no Python call stacks) around one
    ``tracer.span``, one ``block_timer`` and one ``host_span``: the
    spans the ring kept, and the trace read back."""
    from cassmantle_tpu.obs import trace as trace_mod

    ring = Tracer()
    trace_dir = str(tmp_path_factory.mktemp("clock"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)            # compile outside the session
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    global_tracer = trace_mod.tracer
    trace_mod.tracer = ring                 # block_timer records here
    try:
        time.sleep(0.01)
        with ring.span("clock.root", root=True):
            time.sleep(0.02)
            with block_timer("clock.stage_s") as sink:
                sink.append(x @ x)
                time.sleep(0.03)
            with host_span("clock.wait"):
                time.sleep(0.01)
    finally:
        trace_mod.tracer = global_tracer
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    start = dict(data.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("clock."):
                        events[ev.name] = (ev.start_ns, ev.duration_ns)
    (trace_id,) = ring.trace_ids()
    spans = {s["name"]: s for s in ring.get_trace(trace_id)}
    return start, events, spans


@pytest.mark.parametrize("name", ["clock.root", "clock.stage_s",
                                  "clock.wait"])
def test_annotation_and_span_agree_on_start_and_duration(session, name):
    profile_start_ns, events, spans = session
    assert name in events, sorted(events)
    ev_start, ev_duration = events[name]
    span = spans[name]
    assert abs((span["start_ns"] - profile_start_ns) - ev_start) < MS
    assert abs(span["duration_s"] * 1e9 - ev_duration) < MS
    # start_ts stays, for /debugz and the cluster merge: the same instant
    assert span["start_ns"] == round(span["start_ts"] * 1e9)


def test_spans_nest_in_the_trace_as_in_the_ring(session):
    _start, events, spans = session
    root, stage = events["clock.root"], events["clock.stage_s"]
    assert root[0] <= stage[0] and stage[0] + stage[1] <= root[0] + root[1]
    assert spans["clock.stage_s"]["parent_id"] == \
        spans["clock.root"]["span_id"]
    assert spans["clock.wait"]["parent_id"] == spans["clock.root"]["span_id"]
