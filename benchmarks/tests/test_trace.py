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
