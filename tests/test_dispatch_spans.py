"""The host's steps of a dispatch that run while the device may be idle,
each a span of its own (``utils/profiling.host_span``): the prompt LM's
preparation and tail, the image pipelines' preparation and enqueue, and
every collection of the cyclic collector (``host.gc``). Each is observed
where the docs say, as often as they say, and is on the profiler's host
lines under its name, where ``benchmarks/harness/host_trace.py`` finds
it. Tiny test size, real pipelines.
"""

import gc
import glob
import os
import threading
import time

import jax
import pytest

from cassmantle_tpu.config import test_config as tiny_config
from cassmantle_tpu.utils.logging import metrics
from cassmantle_tpu.utils.profiling import install_gc_region

LM_HISTS = ("pipeline.lm_lock_wait_s", "pipeline.lm_prep_s",
            "pipeline.lm_tail_s", "pipeline.prompt_s")
IMAGE_HISTS = ("pipeline.image_prep_s", "pipeline.image_enqueue_s",
               "pipeline.t2i_s", "pipeline.image_host_s")


def totals(names):
    """{histogram: (sum, count)} now."""
    state = metrics.dump_state()["hists"]
    return {name: (sum(h[4] for h in state if h[0] == name),
                   sum(h[5] for h in state if h[0] == name))
            for name in names}


def delta(before, after):
    return {k: (after[k][0] - before[k][0], after[k][1] - before[k][1])
            for k in after}


@pytest.fixture(scope="module")
def backend():
    from cassmantle_tpu.serving.pipeline import TPUContentBackend

    return TPUContentBackend(tiny_config())


def test_an_lm_dispatch_observes_its_preparation_once_and_its_tail_twice(
        backend):
    gen = backend.prompt_gen
    gen.generate_batch(["The storm over the harbor"])   # compiled here
    before = totals(LM_HISTS)
    texts = gen.generate_batch(["The storm over the harbor",
                                "A lamp in the tower"])
    got = delta(before, totals(LM_HISTS))
    assert len(texts) == 2
    assert {k: n for k, (_s, n) in got.items()} == {
        "pipeline.lm_lock_wait_s": 1, "pipeline.lm_prep_s": 1,
        "pipeline.lm_tail_s": 2, "pipeline.prompt_s": 1}
    # decode_ids_batch alone: its preparation and its own part of the tail
    before = totals(LM_HISTS)
    gen.decode_ids_batch(["The storm over the harbor"])
    got = delta(before, totals(LM_HISTS))
    assert got["pipeline.lm_prep_s"][1] == 1
    assert got["pipeline.lm_tail_s"][1] == 1


def test_an_image_dispatch_observes_its_preparation_and_enqueue_once(
        backend):
    backend.t2i.generate(["a lighthouse"], seed=1)     # compiled here
    before = totals(IMAGE_HISTS)
    backend.t2i.generate(["a lighthouse at dusk"], seed=2)
    got = delta(before, totals(IMAGE_HISTS))
    assert {k: n for k, (_s, n) in got.items()} == {
        name: 1 for name in IMAGE_HISTS}
    # the enqueue is inside the device-synchronized dispatch
    assert got["pipeline.image_enqueue_s"][0] <= got["pipeline.t2i_s"][0]


def test_a_collection_is_observed_once_installed():
    install_gc_region()
    install_gc_region()                     # once a process
    from cassmantle_tpu.utils import profiling

    assert gc.callbacks.count(profiling._gc_region) == 1
    before = totals(["host.gc_s"])["host.gc_s"]
    gc.collect()
    after = totals(["host.gc_s"])["host.gc_s"]
    assert after[1] - before[1] >= 1
    assert after[0] > before[0]


def test_a_collection_under_the_registrys_lock_waits_for_the_next():
    """A collection can start inside the registry's own locked sections:
    its observation must not wait there for ever. It is made with the
    next collection that finds the lock free."""
    install_gc_region()
    before = totals(["host.gc_s"])["host.gc_s"]
    done = threading.Event()

    def collect_under_the_lock():
        with metrics._lock:
            gc.collect()
        done.set()

    thread = threading.Thread(target=collect_under_the_lock, daemon=True)
    thread.start()
    assert done.wait(10.0), "a collection waited for the registry's lock"
    gc.collect()
    after = totals(["host.gc_s"])["host.gc_s"]
    assert after[1] - before[1] >= 2        # the held one and this one


def test_the_spans_are_on_the_profilers_host_lines(backend, tmp_path):
    """A CPU profiler session (no Python call stacks) around one LM
    dispatch, one image dispatch and one collection: every new span is
    on the host lines under its name, and the benchmark's loader finds
    them among the program's spans."""
    from benchmarks.harness import host_trace
    from benchmarks.harness.xplane import load_planes

    install_gc_region()
    backend.prompt_gen.generate_batch(["The storm over the harbor"])
    backend.t2i.generate(["a lighthouse"], seed=1)     # compiled first
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        backend.prompt_gen.generate_batch(["A lamp in the tower"])
        backend.t2i.generate(["a lighthouse at dusk"], seed=2)
        gc.collect()
        time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    names = [n for _s, _d, n in
             host_trace.HostTrace(load_planes(path)).spans]
    for name in ("pipeline.lm_lock_wait", "pipeline.lm_prep",
                 "pipeline.prompt_s", "pipeline.image_prep",
                 "pipeline.image_lock_wait", "pipeline.image_enqueue",
                 "pipeline.t2i_s", "pipeline.image_host", "host.gc"):
        assert name in names, (name, sorted(set(names)))
    assert names.count("pipeline.lm_tail") == 2


def test_an_image_behind_an_unfinished_one_waits_for_it_under_its_own_span(
        backend, tmp_path):
    """Two rooms' images at once (the CPU backend runs a tiny image for
    about a second): the second is enqueued while the first runs, waits
    for it under ``pipeline.image_ahead_wait`` and times its own
    dispatch, ``pipeline.t2i_s``, from that wait's end; both spans are
    on the profiler's host lines."""
    from benchmarks.harness import host_trace
    from benchmarks.harness.xplane import load_planes
    from cassmantle_tpu.utils.locks import Turns

    backend.t2i.generate(["a lighthouse"], seed=1)     # compiled first
    turns = Turns()
    tickets = [turns.take() for _ in range(2)]

    def room(index):
        with turns.holding(tickets[index]):
            backend.t2i.generate([f"a lighthouse {index}"], seed=index)

    before = totals(["pipeline.image_ahead_wait_s"])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        threads = [threading.Thread(target=room, args=(i,))
                   for i in range(2)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(60)
    finally:
        jax.profiler.stop_trace()
    assert not any(thread.is_alive() for thread in threads)
    got = delta(before, totals(["pipeline.image_ahead_wait_s"]))
    assert got["pipeline.image_ahead_wait_s"][1] == 1
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    spans = host_trace.HostTrace(load_planes(path)).spans
    (wait,) = [(s, d) for s, d, n in spans
               if n == "pipeline.image_ahead_wait"]
    timed = sorted(s for s, _d, n in spans if n == "pipeline.t2i_s")
    assert len(timed) == 2
    # the first image's dispatch is timed from before the wait, the
    # second's from its end
    assert timed[0] < wait[0] and timed[1] >= wait[0] + wait[1]
