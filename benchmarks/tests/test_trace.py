"""The trace reduction on a trace the test writes itself, against values
worked out by hand."""

import pytest

from benchmarks.harness.trace import reduce_trace, union_ns

# device ops (start, duration, name), ns; window 0..1000
OPS = [(100, 200, "conv"), (250, 100, "fusion"),   # overlap: busy 100..350
       (500, 100, "conv"),                         # busy 500..600
       (900, 300, "vae")]                          # clipped to 900..1000
HOST = [(340, 170, "bench.lm_dispatch"),           # covers gap 350..500
        (590, 320, "bench.image_dispatch")]        # covers gap 600..900


def test_union():
    assert union_ns([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]


def test_busy_idle_ops_and_gaps():
    r = reduce_trace(OPS, HOST, window=(0, 1000))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((250 + 100 + 100) * 1e-9)
    assert r["idle_share"] == pytest.approx(0.55)
    ops = dict(r["device_ops"])
    assert ops["conv"] == pytest.approx(300e-9)
    assert ops["vae"] == pytest.approx(100e-9)   # clipped at the window
    assert r["device_ops"][0][0] == "conv"
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.lm_dispatch"] == pytest.approx(150e-9)
    assert gaps["bench.image_dispatch"] == pytest.approx(300e-9)
    assert gaps["no_benchmark_span"] == pytest.approx(100e-9)  # 0..100


def test_names_are_cut_to_the_operation_and_containers_not_ranked():
    from benchmarks.harness.trace import short_name

    assert short_name("%fusion.6140 = bf16[32,16]{3,1} fusion(bf16[3]...)") \
        == "fusion"
    assert short_name("%_flash_bhsd.156 = bf16[16,4096,40] custom-call(") \
        == "_flash_bhsd"
    assert short_name("bench.image_dispatch") == "bench.image_dispatch"
    r = reduce_trace([(0, 1000, "while"), (100, 200, "conv")], [],
                     window=(0, 1000))
    assert r["idle_share"] == pytest.approx(0.0)
    assert dict(r["device_ops"]) == {"conv": pytest.approx(200e-9)}


def test_default_window_and_empty():
    r = reduce_trace(OPS[:3], [])
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["idle_share"] == pytest.approx(150 / 500)
    assert reduce_trace([], HOST) == {}


def test_instruction_table_keeps_sites_apart_and_counts_whole_calls():
    """For the readers: seconds and calls by short name, whole HLO text
    and scope. A call the window cuts is in ``device_ops`` with the part
    inside, and not in the table, so that seconds over calls is a call's
    time; the result line's ranking stays by short name."""
    self_hlo = "%flash_attention.3 = bf16[2,4096,320]{2,1,0} custom-call(...)"
    cross_hlo = "%flash_attention.4 = bf16[2,4096,320]{2,1,0} custom-call(..)"
    ops = [(0, 100, "flash_attention", self_hlo, "UNet/up_0/self_attn:"),
           (100, 20, "flash_attention", cross_hlo, "UNet/up_0/cross_attn:"),
           (120, 100, "flash_attention", self_hlo, "UNet/up_0/self_attn:"),
           (220, 30, "fusion", "%fusion.1 = f32[8]{0} fusion(...)", ""),
           (250, 700, "while", "%while.1 = (...) while(...)", ""),
           (950, 100, "flash_attention", self_hlo, "UNet/up_0/self_attn:")]
    r = reduce_trace(ops, [], window=(0, 1000))
    assert dict(r["device_ops"])["flash_attention"] == pytest.approx(270e-9)
    rows = {(i["name"], i["hlo"], i["scope"]): i for i in r["instructions"]}
    assert len(rows) == 3          # the container is not an instruction
    one = rows[("flash_attention", self_hlo, "UNet/up_0/self_attn:")]
    assert one["calls"] == 2 and one["seconds"] == pytest.approx(200e-9)
    two = rows[("flash_attention", cross_hlo, "UNet/up_0/cross_attn:")]
    assert two["calls"] == 1 and two["seconds"] == pytest.approx(20e-9)
    # intervals with a name alone (the older form) still reduce
    plain = reduce_trace(OPS, HOST, window=(0, 1000))["instructions"]
    assert {(i["name"], i["hlo"], i["calls"]) for i in plain} == {
        ("conv", "", 2), ("fusion", "", 1)}


def test_a_chips_trace_reaches_the_table_with_hlo_text_and_scope(tmp_path):
    """chip_probe.xplane.pb: one v5e chip's trace of a small program that
    calls the flash kernel under a scope, three dispatches (PR 26). The
    profiler's own reader has no scope; the file's wire format has."""
    import os
    import shutil

    from benchmarks.harness.trace import reduce_xplane

    run = tmp_path / "plugins" / "profile" / "2026_10_01"
    run.mkdir(parents=True)
    shutil.copy(os.path.join(os.path.dirname(__file__),
                             "chip_probe.xplane.pb"), run / "t.xplane.pb")
    r = reduce_xplane(str(tmp_path))
    assert 0 < r["busy_s"] <= r["window_s"]
    (flash,) = [i for i in r["instructions"]
                if i["name"] == "flash_attention"]
    assert flash["calls"] == 3
    assert flash["seconds"] == pytest.approx(3 * 79.8e-6, rel=0.01)
    assert flash["hlo"].startswith(
        "%flash_attention.1 = bf16[16,1024,40]{2,1,0:T(8,128)(2,1)S(1)} "
        "custom-call(bf16[16,1024,40]")
    assert flash["scope"] == ("jit(probe_program)/stage_b/jit(_flash_bhsd)/"
                              "flash_attention/pallas_call:")
    assert dict(r["device_ops"])["flash_attention"] == pytest.approx(
        flash["seconds"])
