"""Why the chip waits, read by the program's own spans: the benchmark's
``harness/host_trace.py`` and the readers over it, as tier-1 tests (the
tier-1 command does not collect ``benchmarks/tests``).

The attribution rule runs on a trace written by hand, laid out as the
profiler lays one out (``xplane.load_planes``' planes), against values
worked out by hand; the program reader on one chip's real trace; and the
benchmark's existing reduction of that trace is pinned to what it gave
before this module was added.
"""

import hashlib
import json
import os
import shutil

import pytest

from benchmarks.harness import host_trace
from benchmarks.harness.trace import reduce_trace, reduce_xplane
from benchmarks.readers import (
    hist_share,
    hist_sum_mean,
    trace_idle_under,
    trace_module_ms,
)

PROBE = os.path.join(os.path.dirname(host_trace.__file__), os.pardir,
                     "tests", "chip_probe.xplane.pb")
LM = ["pipeline.lm_prep", "pipeline.lm_tail", "decode.verify_s"]
IMAGE = ["pipeline.image_prep", "pipeline.image_enqueue",
         "pipeline.image_host"]


def ev(start, duration, name):
    return (start, duration, name, {})


# the device busy 0-100, 300-400, 600-700, 900-1000 ns: idle 600 of the
# window 0..1000, which the benchmark's two spans bound
OPS = [(0, 100, "fusion"), (300, 50, "while"), (320, 80, "conv"),
       (600, 100, "fusion"), (900, 100, "vae")]
BENCH = [ev(0, 500, "bench.lm_dispatch"), ev(500, 500, "bench.image_dispatch")]
# two host threads; on the first a round, the LM's preparation with a
# collection inside it, then its tail; on the second the image's lock wait
# over its enqueue (a work span under a wait), its tail, then the queue's
# wait, which reaches past the round: 850-900 is idle under no span
THREAD_A = [ev(0, 850, "round.content"), ev(120, 100, "pipeline.lm_prep"),
            ev(150, 20, "host.gc"), ev(380, 60, "pipeline.lm_tail")]
THREAD_B = [ev(450, 200, "pipeline.image_lock_wait"),
            ev(500, 50, "pipeline.image_enqueue"),
            ev(720, 30, "pipeline.image_host"),
            ev(760, 200, "prompt.queue_wait"),
            ev(10, 5, "PjitFunction(lm_decode)")]
# by hand, ns of idle: gap 100-300: round 20, lm_prep 30 + 50, gc 20,
# round 80; gap 400-600: lm_tail 40, round 60, image_enqueue 50, round 50;
# gap 700-900: round 20, image_host 30, round 100, no span 50
BY_HAND = {"round.content": 330, "pipeline.lm_prep": 80, "host.gc": 20,
           "pipeline.lm_tail": 40, "pipeline.image_enqueue": 50,
           "pipeline.image_host": 30, host_trace.BETWEEN: 50}


def planes():
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ev(0, 400, "jit_lm_decode(123)"),
            ev(600, 100, "jit_t2i_sample(9)"),
            ev(900, 150, "jit_t2i_sample(9)")]},
        {"name": "XLA Ops", "events": [ev(*op) for op in OPS]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": THREAD_A + BENCH},
        {"name": "cassmantle-dispatch", "events": THREAD_B}]}
    return [device, host, {"name": "Task Environment", "lines": []}]


def test_every_idle_nanosecond_goes_to_the_shortest_open_span_once():
    trace = host_trace.HostTrace(planes())
    assert trace.window == (0, 1000)
    assert sorted(n for _s, _d, n in trace.spans) == sorted(
        e[2] for e in THREAD_A + THREAD_B if not e[2].startswith("Pjit"))
    assert trace.idle == {k: pytest.approx(v / 1000)
                          for k, v in BY_HAND.items()}
    # the same window and busy union as the benchmark's idle share
    reduced = reduce_trace(OPS, [e[:3] for e in BENCH], window=(0, 1000))
    assert sum(trace.idle.values()) == pytest.approx(reduced["idle_share"])
    assert reduced["idle_share"] == pytest.approx(0.6)


@pytest.mark.parametrize("args, want", [
    ({"spans": LM}, 12.0),
    ({"spans": IMAGE}, 8.0),
    ({"outside": LM + IMAGE}, 40.0),
    ({"spans": ["round.content"]}, 33.0),
], ids=["lm_group", "image_group", "between", "one_span"])
def test_the_idle_readers_partition_the_idle_share(monkeypatch, args, want):
    trace = host_trace.HostTrace(planes())
    monkeypatch.setattr(host_trace, "of_run", lambda ctx: trace)
    assert trace_idle_under.read({}, args) == pytest.approx(want)
    groups = [trace_idle_under.read({}, a) for a in (
        {"spans": LM}, {"spans": IMAGE}, {"outside": LM + IMAGE})]
    assert sum(groups) == pytest.approx(60.0)


def test_shares_average_over_the_devices_as_the_idle_share_does():
    """Two devices: each its own gaps under the same spans, the shares
    their mean, summing to the mean idle share."""
    spans = [(s, d, n) for s, d, n, _ in THREAD_A + THREAD_B]
    devices = {"/device:TPU:0": [(s, d) for s, d, _ in OPS],
               "/device:TPU:1": [(0, 500)], "/device:TPU:2": []}
    shares = host_trace.idle_by_span(devices, spans, window=(0, 1000))
    # device 1 idle 500-1000: lm_tail 0 (ends at 440), enqueue 500-550,
    # round to 850 but for image_host 720-750, then no span
    second = {"pipeline.image_enqueue": 50, "round.content": 270,
              "pipeline.image_host": 30, host_trace.BETWEEN: 150}
    for name in set(BY_HAND) | set(second):
        want = (BY_HAND.get(name, 0) + second.get(name, 0)) / 2 / 1000
        assert shares.get(name, 0.0) == pytest.approx(want), name
    assert sum(shares.values()) == pytest.approx((0.6 + 0.5) / 2)


def test_a_program_reads_by_its_name_over_whole_executions_in_the_window():
    trace = host_trace.HostTrace(planes())
    assert trace.module_ms("jit_lm_decode") == pytest.approx(400e-6)
    # the second sampler run ends past the window: one whole execution
    assert trace.module_ms("jit_t2i_sample") == pytest.approx(100e-6)
    assert trace.module_ms("jit_scorer_encode") is None


@pytest.fixture()
def probe_dir(tmp_path):
    run = tmp_path / "plugins" / "profile" / "2026_10_01"
    run.mkdir(parents=True)
    shutil.copy(PROBE, run / "t.xplane.pb")
    return str(tmp_path)


def test_a_chips_programs_read_by_name(probe_dir):
    """chip_probe.xplane.pb: three executions of ``jit_probe_program`` on
    one v5e chip (102,212, 102,197 and 102,528 ns on its ``XLA Modules``
    line); no benchmark span, so every execution counts."""
    ctx = {"trace": {"window_s": 1.0}, "trace_dir": probe_dir}
    got = trace_module_ms.read(ctx, {"module": "jit_probe_program"})
    assert got == pytest.approx((102212 + 102197 + 102528) / 3 / 1e6)
    assert trace_module_ms.read(ctx, {"module": "jit_lm_decode"}) is None
    # the probe's own host spans are no program span: all idle is between
    idle = trace_idle_under.read(ctx, {"outside": LM + IMAGE})
    assert idle == pytest.approx(100 * reduce_xplane(probe_dir)["idle_share"])
    assert trace_idle_under.read(ctx, {"spans": LM}) == 0.0


def test_an_untraced_run_reads_nothing(probe_dir):
    ctx = {"trace": None, "trace_dir": probe_dir}
    assert trace_module_ms.read(ctx, {"module": "jit_probe_program"}) is None
    assert trace_idle_under.read(ctx, {"spans": LM}) is None
    assert host_trace.of_run({"trace": {"x": 1},
                              "trace_dir": probe_dir + "/none"}) is None


def test_the_benchmarks_reduction_of_a_chips_trace_is_unchanged(probe_dir):
    """``reduce_xplane`` on chip_probe.xplane.pb gives what it gave before
    ``host_trace`` was added beside it: the readable part, and a digest of
    the whole reduction (sorted-key JSON)."""
    got = reduce_xplane(probe_dir)
    assert got["window_s"] == 0.007586411
    assert got["busy_s"] == 0.000303715
    assert got["idle_share"] == 0.9599659180078696
    assert got["n_device_ops"] == 66
    assert got["idle_gaps"] == [["no_benchmark_span", 0.007282696]]
    assert got["device_ops"][:3] == [["flash_attention", 0.000239528],
                                     ["copy", 3.8466e-05],
                                     ["convolution_tanh_fusion", 1.2691e-05]]
    assert len(got["instructions"]) == 15
    digest = hashlib.sha256(json.dumps(got, sort_keys=True).encode())
    assert digest.hexdigest() == (
        "c0c7f995e9fba98ef64b16e6157193388f7445775e27a377afe0e652c47067cd")


def test_every_lock_wait_span_is_named_as_a_wait():
    """The rule leaves out waits by name (``host_trace.is_wait``): every
    ``wait_span=`` the program gives a lock has to end so."""
    import ast
    import pathlib

    import cassmantle_tpu

    root = pathlib.Path(cassmantle_tpu.__file__).parent
    found = []
    for path in root.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.keyword) and node.arg == "wait_span" \
                    and isinstance(node.value, ast.Constant):
                found.append(node.value.value)
    assert "pipeline.lm_lock_wait" in found
    assert "pipeline.image_lock_wait" in found
    assert all(host_trace.is_wait(name) for name in found), found


def test_the_wait_for_the_image_ahead_is_a_wait():
    """An image dispatch that waits under the lock for the image ahead
    (``pipeline.image_ahead_wait``) waits: idle under it goes to the
    work span open around it, here the round."""
    assert host_trace.is_wait("pipeline.image_ahead_wait")
    spans = [(0, 1000, "round.content"),
             (100, 300, "pipeline.image_ahead_wait")]
    devices = {"/device:TPU:0": [(0, 100), (400, 600)]}
    assert host_trace.idle_by_span(devices, spans, window=(0, 1000)) == {
        "round.content": pytest.approx(0.3)}


class FakeWindow:
    """``runner.Window``'s reading of two histogram snapshots."""

    def __init__(self, after: dict, before: dict) -> None:
        self.after, self.before = after, before

    def hist(self, name):
        s1, c1 = self.after.get(name, (0.0, 0))
        s0, c0 = self.before.get(name, (0.0, 0))
        return s1 - s0, c1 - c0


def test_host_time_a_dispatch_sums_its_parts_over_one_count():
    window = FakeWindow(
        after={"pipeline.lm_prep_s": (0.5, 12),
               "pipeline.lm_tail_s": (0.9, 24)},
        before={"pipeline.lm_prep_s": (0.2, 2),
                "pipeline.lm_tail_s": (0.3, 4)})
    args = {"hists": ["pipeline.lm_prep_s", "pipeline.lm_tail_s"],
            "per": "pipeline.lm_prep_s", "scale": 1000}
    # (0.3 + 0.6) s over 10 dispatches
    assert hist_sum_mean.read({"window": window}, args) == pytest.approx(90.0)
    parent = FakeWindow(after={}, before={})
    assert hist_sum_mean.read({"window": parent}, args) is None


def test_a_histograms_share_of_the_window():
    window = FakeWindow(after={"host.gc_s": (0.25, 40)},
                        before={"host.gc_s": (0.05, 30)})
    ctx = {"window": window, "window_s": 10.0}
    assert hist_share.read(ctx, {"hist": "host.gc_s"}) == pytest.approx(2.0)
    # no collection in the window, or a program without the histogram:
    # nothing to read
    quiet = FakeWindow(after={"host.gc_s": (0.05, 30)},
                       before={"host.gc_s": (0.05, 30)})
    parent = FakeWindow(after={}, before={})
    for window in (quiet, parent):
        assert hist_share.read(dict(ctx, window=window),
                               {"hist": "host.gc_s"}) is None
