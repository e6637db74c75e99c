"""Device cost & capacity observability (ISSUE 14): HBM telemetry with
explicit CPU degradation, compile wall-time recording, cache hit/miss
mirrors, roofline attribution on the warmed serving paths, the
`/readyz` device block, the `/debug/trace` gate, and the cost-model
drift gate. Fast tier (tests/conftest.py)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.config import test_config as _tiny_config
from cassmantle_tpu.obs import costmodel
from cassmantle_tpu.obs.device import DeviceMetrics
from cassmantle_tpu.utils import jit_sentinel
from cassmantle_tpu.utils.logging import Metrics, metrics


class _FakeDevice:
    def __init__(self, platform="tpu", dev_id=0, stats=None):
        self.platform = platform
        self.id = dev_id
        self._stats = stats

    def memory_stats(self):
        return self._stats


class _NoStatsDevice:
    """Old runtime: no memory_stats attribute at all."""

    platform = "tpu"
    id = 0


def _gauges(reg):
    return reg.snapshot()["gauges"]


# -- CPU-host degradation: explicit unavailable marker, never zeros --------

def test_memory_stats_none_marks_unavailable_not_zero():
    """A device whose memory_stats() returns None (the CPU backend)
    exports hbm_available=0 and NO hbm byte gauges at all — an all-zero
    worker would read as an empty chip and attract load."""
    reg = Metrics()
    dm = DeviceMetrics(registry=reg,
                       devices_fn=lambda: [_FakeDevice(stats=None)])
    seen = dm.sample()
    assert seen == {"tpu:0": None}
    gauges = _gauges(reg)
    assert gauges['device.hbm_available{device="tpu:0"}'] == 0.0
    assert not any(k.startswith("device.hbm_bytes") for k in gauges)
    assert not any(k.startswith("device.hbm_peak") for k in gauges)
    block = dm.device_block()
    assert block["devices"]["tpu:0"] == "unavailable"


def test_memory_stats_attribute_missing_marks_unavailable():
    reg = Metrics()
    dm = DeviceMetrics(registry=reg,
                       devices_fn=lambda: [_NoStatsDevice()])
    assert dm.sample() == {"tpu:0": None}
    assert _gauges(reg)['device.hbm_available{device="tpu:0"}'] == 0.0


def test_memory_stats_raising_marks_unavailable():
    class Raising(_FakeDevice):
        def memory_stats(self):
            raise RuntimeError("backend wedged")

    reg = Metrics()
    dm = DeviceMetrics(registry=reg,
                       devices_fn=lambda: [Raising()])
    assert dm.sample() == {"tpu:0": None}
    assert _gauges(reg)['device.hbm_available{device="tpu:0"}'] == 0.0


def test_sample_never_initializes_a_backend(monkeypatch):
    """A telemetry read must never be the thing that initializes a jax
    backend: --fake drill workers are accelerator-free, and on a TPU
    host an auxiliary worker would contend for the single-client
    runtime. With no backend initialized, sample() reports nothing."""
    from jax._src import xla_bridge

    reg = Metrics()
    dm = DeviceMetrics(registry=reg)
    monkeypatch.setattr(xla_bridge, "_backends", {})
    assert dm.sample() == {}
    assert not _gauges(reg)
    dm.note_dispatch("t2i")
    assert dm.highwater() == {}


def test_real_cpu_device_degrades_explicitly():
    """The ACTUAL CPU backend (tier-1's only device) must take the
    unavailable path end to end — jaxlib returns None there."""
    jax.local_devices()   # initialize the backend (the guard requires it)
    reg = Metrics()
    dm = DeviceMetrics(registry=reg)
    seen = dm.sample()
    assert seen, "no local devices visible"
    label = next(iter(seen))
    assert seen[label] is None
    assert _gauges(reg)[f'device.hbm_available{{device="{label}"}}'] == 0.0
    assert dm.device_block()["devices"][label] == "unavailable"


def test_hbm_stats_export_gauges():
    stats = {"bytes_in_use": 1_000, "bytes_limit": 16_000,
             "peak_bytes_in_use": 2_000}
    reg = Metrics()
    dm = DeviceMetrics(
        registry=reg,
        devices_fn=lambda: [_FakeDevice(dev_id=3, stats=stats)])
    dm.sample()
    gauges = _gauges(reg)
    assert gauges['device.hbm_bytes_in_use{device="tpu:3"}'] == 1_000
    assert gauges['device.hbm_bytes_limit{device="tpu:3"}'] == 16_000
    assert gauges['device.hbm_peak_bytes{device="tpu:3"}'] == 2_000
    assert gauges['device.hbm_available{device="tpu:3"}'] == 1.0
    block = dm.device_block()
    assert block["devices"]["tpu:3"] == {
        "bytes_in_use": 1_000, "bytes_limit": 16_000,
        "peak_bytes_in_use": 2_000}


def test_partial_stats_export_what_exists():
    reg = Metrics()
    dm = DeviceMetrics(
        registry=reg,
        devices_fn=lambda: [_FakeDevice(stats={"bytes_in_use": 7})])
    dm.sample()
    gauges = _gauges(reg)
    assert gauges['device.hbm_bytes_in_use{device="tpu:0"}'] == 7
    assert 'device.hbm_bytes_limit{device="tpu:0"}' not in gauges
    assert gauges['device.hbm_available{device="tpu:0"}'] == 1.0


def test_telemetry_going_dark_retracts_byte_gauges():
    """A device whose memory_stats starts failing MID-FLIGHT must not
    keep serving its last byte readings as current truth: the next
    sample flips hbm_available to 0 AND retracts the byte gauges (a
    frozen occupancy number would steer an autoscaler indefinitely)."""
    dev = _FakeDevice(stats={"bytes_in_use": 123, "bytes_limit": 456})
    reg = Metrics()
    dm = DeviceMetrics(registry=reg, devices_fn=lambda: [dev])
    dm.sample()
    assert _gauges(reg)['device.hbm_bytes_in_use{device="tpu:0"}'] == 123
    dev._stats = None                      # runtime hiccup: went dark
    dm.sample()
    gauges = _gauges(reg)
    assert gauges['device.hbm_available{device="tpu:0"}'] == 0.0
    assert not any(k.startswith("device.hbm_bytes") for k in gauges)
    assert dm.device_block()["devices"]["tpu:0"] == "unavailable"
    # ...and a recovered device re-exports
    dev._stats = {"bytes_in_use": 200}
    dm.sample()
    assert _gauges(reg)['device.hbm_bytes_in_use{device="tpu:0"}'] == 200


def test_highwater_tracks_max_per_pipeline():
    stats = {"bytes_in_use": 100}
    reg = Metrics()
    dm = DeviceMetrics(registry=reg,
                       devices_fn=lambda: [_FakeDevice(stats=stats)])
    dm.note_dispatch("t2i")
    stats["bytes_in_use"] = 500
    dm.note_dispatch("t2i")
    stats["bytes_in_use"] = 250   # lower sample must not regress the max
    dm.note_dispatch("t2i")
    dm.note_dispatch("prompt")
    assert dm.highwater() == {"t2i": 500, "prompt": 250}
    gauges = _gauges(reg)
    assert gauges['device.hbm_highwater_bytes{pipeline="t2i"}'] == 500
    assert gauges['device.hbm_highwater_bytes{pipeline="prompt"}'] == 250


def test_highwater_noop_without_telemetry():
    reg = Metrics()
    dm = DeviceMetrics(registry=reg,
                       devices_fn=lambda: [_FakeDevice(stats=None)])
    dm.note_dispatch("t2i")
    assert dm.highwater() == {}
    assert not any("highwater" in k for k in _gauges(reg))


# -- compile wall time (utils/jit_sentinel.py) ------------------------------

def _hist_total(name):
    totals = metrics.hist_totals(name)
    return totals[2] if totals else 0


def test_compile_wall_time_recorded_then_quiet():
    """A fresh compile lands a jit.compile_s observation, bumps the
    cumulative jit.compile_seconds counter, and names the function in
    the snapshot; a warmed steady-state call records NOTHING (the
    acceptance bar: at least one observation during warmup, zero
    after). The autouse fixture armed + reset the sentinel."""
    assert jit_sentinel.sentinel_active()

    def obs_device_warmup_fn(x):
        return x * 3 + 1

    fn = jax.jit(obs_device_warmup_fn)
    before_hist = _hist_total("jit.compile_s")
    before_counter = metrics.counter_total("jit.compile_seconds")
    fn(jnp.ones((8,))).block_until_ready()      # warmup: compiles
    after_warmup = _hist_total("jit.compile_s")
    assert after_warmup > before_hist
    assert metrics.counter_total("jit.compile_seconds") > before_counter
    snap = jit_sentinel.compile_time_snapshot()
    assert snap.get("obs_device_warmup_fn", 0) > 0
    # steady state: same shapes, warmed cache — zero new observations
    fn(jnp.ones((8,))).block_until_ready()
    assert _hist_total("jit.compile_s") == after_warmup


def test_compile_time_parser_handles_finished_record():
    from cassmantle_tpu.utils.jit_sentinel import (
        _parse_finished,
        compile_time_snapshot,
        reset_counts,
    )

    reset_counts()
    _parse_finished(
        "Finished XLA compilation of jit(my_fn) in 2.5 sec")
    assert compile_time_snapshot() == {"my_fn": 2.5}
    # malformed tails must be ignored, never raise
    _parse_finished("Finished XLA compilation of jit(x) in soon")
    _parse_finished("Finished XLA compilation of nonsense")
    assert compile_time_snapshot() == {"my_fn": 2.5}
    reset_counts()


def test_slow_compile_lands_in_flight_recorder():
    """Compiles >= 1 s land in /debugz (kind jit.compile); sub-second
    warmup bursts stay metric-only so they can't flush the event ring
    of the supervision story."""
    from cassmantle_tpu.obs.recorder import flight_recorder
    from cassmantle_tpu.utils.jit_sentinel import _record_compile_time

    _record_compile_time("jit(tiny_fn)", 0.01)
    _record_compile_time("jit(sdxl_sample)", 97.2)
    events = flight_recorder.tail(50, kind="jit.compile")
    fns = [e["fn"] for e in events]
    assert "sdxl_sample" in fns
    assert "tiny_fn" not in fns
    jit_sentinel.reset_counts()


# -- persistent-compile-cache hit/miss mirrors ------------------------------

def test_cache_event_listener_mirrors_gauges():
    from cassmantle_tpu.utils import compile_cache

    compile_cache._arm_cache_listener()
    before = compile_cache.cache_event_counts()
    # drive jax.monitoring's real listener fan-out, no compile needed
    from jax import monitoring

    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    after = compile_cache.cache_event_counts()
    assert after["misses"] == before["misses"] + 1
    assert after["hits"] == before["hits"] + 2
    gauges = metrics.snapshot()["gauges"]
    assert gauges["jit.cache_hits"] == after["hits"]
    assert gauges["jit.cache_misses"] == after["misses"]


# -- the chip's peak: one table keyed by device_kind, no default ------------

PEAK_CASES = [
    # (device_kind, peak bf16 FLOP/s or None = not in the table)
    ("TPU v5 lite", 197e12),
    ("TPU v9 imaginary", None),
]


@pytest.mark.parametrize("kind,peak", PEAK_CASES)
def test_peak_table_is_keyed_by_device_kind(kind, peak):
    """v5e's device_kind resolves to its published peak with the source
    beside it; a kind the table does not hold is an error, never a
    default (a 197 TFLOP/s guess for an unknown chip made every
    utilization on it wrong without saying so)."""
    if peak is None:
        with pytest.raises(costmodel.UnknownDeviceKind, match=kind):
            costmodel.peak_flops_for_kind(kind)
        return
    assert costmodel.peak_flops_for_kind(kind) == peak
    assert "Google Cloud" in costmodel.CHIP_PEAKS[kind]["source"]


def test_no_mxu_gauge_off_tpu():
    """On the CPU there is no peak to divide by: an attributed dispatch
    still counts its FLOPs but renders no utilization gauge or span
    attr (a CPU ratio under a device metric's name is a wrong number,
    not a small one)."""
    from cassmantle_tpu.utils.profiling import block_timer

    assert costmodel.chip_peak_flops() is None
    before = metrics.counter_total("request.device_flops")
    with block_timer("test.cpu_dispatch_s", flops_est=1e9,
                     pipeline="cpu_only"):
        pass
    assert metrics.counter_total("request.device_flops") == before + 1e9
    assert _pipeline_gauge("pipeline.mxu_utilization", "cpu_only") is None


# -- roofline attribution: the warmed serving smoke (acceptance) ------------

@pytest.fixture(scope="module")
def tiny_cfg():
    return _tiny_config()


@pytest.fixture
def v5e_peak(monkeypatch):
    """Steer the gauge's peak lookup to the v5e row, so the attribution
    arithmetic below runs on the CPU host (which has no peak, and so
    no gauge, of its own)."""
    monkeypatch.setattr(
        costmodel, "chip_peak_flops",
        lambda: costmodel.peak_flops_for_kind("TPU v5 lite"))


def _pipeline_gauge(name, pipeline):
    return metrics.snapshot()["gauges"].get(
        f'{name}{{pipeline="{pipeline}"}}')


def _spans_named(trace_id, name):
    from cassmantle_tpu.obs.trace import tracer

    return [s for s in (tracer.get_trace(trace_id) or [])
            if s["name"] == name]


def test_t2i_dispatch_carries_flops_and_mxu(tiny_cfg, v5e_peak):
    """The acceptance smoke, image path: a warmed generate produces a
    stage span carrying flops_est attrs, a nonzero
    pipeline.mxu_utilization{pipeline=t2i} gauge, and a
    request.device_flops delta — and the warmed dispatch records zero
    jit.compile_s observations (sentinel still zero-recompile)."""
    from cassmantle_tpu.obs.trace import tracer
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    pipe = Text2ImagePipeline(tiny_cfg)
    pipe.generate(["warmup"], seed=1)           # compiles
    compile_obs = _hist_total("jit.compile_s")
    flops_before = metrics.counter_total("request.device_flops")
    with tracer.span("test.t2i", root=True) as span:
        with jit_sentinel.no_new_compiles():
            pipe.generate(["a storm over the harbor"], seed=2)
    stage = _spans_named(span.trace_id, "pipeline.t2i_s")
    assert stage, "no device stage span recorded"
    assert stage[-1]["attrs"]["flops_est"] > 0
    assert stage[-1]["attrs"]["mxu_utilization"] > 0
    assert metrics.counter_total("request.device_flops") > flops_before
    mxu = _pipeline_gauge("pipeline.mxu_utilization", "t2i")
    assert mxu is not None and mxu > 0
    # warmup observed compile_s at least once; warmed dispatch: zero
    assert compile_obs > 0
    assert _hist_total("jit.compile_s") == compile_obs


def test_t2i_flops_estimate_matches_analytic_trace(tiny_cfg):
    """The per-dispatch estimate equals a direct trace of the pipeline
    impl (the committed artifact never matches the test config, so the
    trace-once fallback is the path under test)."""
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    pipe = Text2ImagePipeline(tiny_cfg)
    per_image = pipe._dispatch_flops(pipe._sample, tiny_cfg.sampler)
    ids = jax.ShapeDtypeStruct((1, pipe.pad_len), jnp.int32)
    expect, _ = costmodel.trace_cost(
        pipe._sample_impl, pipe._params, ids, ids, jax.random.PRNGKey(0))
    assert per_image == pytest.approx(expect, rel=1e-6)
    # cached: second resolution returns the same object fast
    assert pipe._dispatch_flops(pipe._sample, tiny_cfg.sampler) \
        == per_image


def test_failed_dispatch_attributes_no_flops():
    """A dispatch that raises did not do its analytic FLOPs: no
    request.device_flops, no mxu gauge spike from a short
    elapsed-at-failure (attribution is success-gated)."""
    from cassmantle_tpu.utils.profiling import block_timer

    before = metrics.counter_total("request.device_flops")
    with pytest.raises(RuntimeError):
        with block_timer("pipeline.t2i_s", flops_est=1e18,
                         pipeline="t2i"):
            raise RuntimeError("chaos: device OOM mid-dispatch")
    assert metrics.counter_total("request.device_flops") == before


def test_tier_variant_flops_resolve_in_background(tiny_cfg):
    """A brownout-tier variant engages exactly when the system sheds
    latency: its cost trace must run off-thread — first resolutions
    answer None (no attribution), the cached figure appears shortly."""
    import dataclasses
    import time

    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    pipe = Text2ImagePipeline(tiny_cfg)
    scfg = dataclasses.replace(tiny_cfg.sampler, num_steps=2)
    assert pipe._dispatch_flops(pipe._sample, scfg) is None
    got = None
    deadline = time.time() + 30
    while time.time() < deadline:
        got = pipe._dispatch_flops(pipe._sample, scfg)
        if got is not None:
            break
        time.sleep(0.05)
    assert got is not None and got > 0


def test_prompt_dispatch_carries_flops(tiny_cfg, v5e_peak):
    from cassmantle_tpu.obs.trace import tracer
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    gen = PromptGenerator(tiny_cfg)
    gen.generate_batch(["warm"])                # compiles
    with tracer.span("test.prompt", root=True) as span:
        gen.generate_batch(["the tide rose", "a lantern flickered"])
    stage = _spans_named(span.trace_id, "pipeline.prompt_s")
    assert stage and stage[-1]["attrs"]["flops_est"] > 0
    # 2N flops/token × dispatched tokens (buckets are shape-exact)
    n = costmodel.params_count(gen.params)
    assert gen._token_flops() == pytest.approx(2.0 * n)
    mxu = _pipeline_gauge("pipeline.mxu_utilization", "prompt")
    assert mxu is not None and mxu > 0


def test_scorer_dispatch_carries_flops(tiny_cfg, v5e_peak):
    from cassmantle_tpu.obs.trace import tracer
    from cassmantle_tpu.ops.scorer import EmbeddingScorer

    scorer = EmbeddingScorer(tiny_cfg.models.minilm, seq_len=8,
                             batch_buckets=(4,))
    scorer.embed(["warm"])                      # compiles
    with tracer.span("test.scorer", root=True) as span:
        scorer.embed(["storm", "harbor"])
    stage = _spans_named(span.trace_id, "scorer.encode_s")
    assert stage and stage[-1]["attrs"]["flops_est"] > 0
    mxu = _pipeline_gauge("pipeline.mxu_utilization", "scorer")
    assert mxu is not None and mxu > 0


def test_committed_cost_model_resolves_without_tracing():
    """A signature match against the committed artifact short-circuits
    the trace (production configs pay zero startup tracing)."""
    model = costmodel.load_cost_model()
    entry = model["pipelines"]["t2i"]
    calls = []

    def tracer_fn():
        calls.append(1)
        return 1.0

    costmodel.reset_runtime_cache()
    try:
        got = costmodel.flops_per_item("t2i", entry["signature"],
                                       tracer=tracer_fn)
        assert got == entry["flops_per_item"]
        assert not calls
        # mismatched signature falls to the tracer, cached once
        got2 = costmodel.flops_per_item("t2i", "no-such-sig",
                                        tracer=tracer_fn)
        assert got2 == 1.0 and calls == [1]
        costmodel.flops_per_item("t2i", "no-such-sig", tracer=tracer_fn)
        assert calls == [1]
    finally:
        costmodel.reset_runtime_cache()


def test_failing_tracer_degrades_to_none():
    costmodel.reset_runtime_cache()
    try:
        def boom():
            raise RuntimeError("trace failed")

        assert costmodel.flops_per_item("t2i", "sig-x",
                                        tracer=boom) is None
        # and the failure is cached — not retried per dispatch
        assert costmodel.flops_per_item("t2i", "sig-x") is None
    finally:
        costmodel.reset_runtime_cache()


# -- /readyz device block + /debug/trace gate -------------------------------

async def _make_client(cfg):
    import dataclasses

    from aiohttp.test_utils import TestClient, TestServer

    from cassmantle_tpu.engine.content import (
        FakeContentBackend,
        hash_embed,
        hash_similarity,
    )
    from cassmantle_tpu.engine.game import Game
    from cassmantle_tpu.engine.store import MemoryStore
    from cassmantle_tpu.server.app import create_app

    cfg = cfg.replace(game=dataclasses.replace(
        cfg.game, rate_limit_default=1000.0, rate_limit_api=1000.0))
    game = Game(cfg, MemoryStore(), FakeContentBackend(image_size=32),
                hash_embed, hash_similarity)
    app = create_app(game, cfg, start_timer=False)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


@pytest.mark.asyncio
async def test_readyz_embeds_device_telemetry(tiny_cfg):
    jax.local_devices()   # serving processes have a backend up; so do we
    client = await _make_client(tiny_cfg)
    try:
        res = await client.get("/readyz")
        body = await res.json()
        block = body["device_telemetry"]
        # CPU host: every device explicitly unavailable, never zeros
        assert block["devices"]
        assert all(v == "unavailable" for v in block["devices"].values())
        assert "hbm_highwater_bytes" in block
        compile_block = block["compile"]
        assert {"functions", "compiles", "total_s",
                "slowest"} <= set(compile_block)
    finally:
        await client.close()


@pytest.mark.asyncio
async def test_metrics_scrape_refreshes_device_gauges(tiny_cfg):
    jax.local_devices()
    client = await _make_client(tiny_cfg)
    try:
        res = await client.get("/metrics")
        gauges = (await res.json())["gauges"]
        avail = [v for k, v in gauges.items()
                 if k.startswith("device.hbm_available")]
        assert avail and all(v == 0.0 for v in avail)  # CPU backend
    finally:
        await client.close()


@pytest.mark.asyncio
async def test_debug_trace_gated_like_debugz(tiny_cfg, monkeypatch):
    """Loopback passes (status quo); a non-loopback caller needs the
    cluster token (the /debugz gate, ISSUE 14) — and a successful
    capture counts obs.profiler_captures."""
    from cassmantle_tpu.server import app as app_mod

    client = await _make_client(tiny_cfg)
    try:
        before = metrics.counter_total("obs.profiler_captures")
        res = await client.post("/debug/trace?seconds=0.05&name=gate")
        assert res.status == 200
        assert metrics.counter_total("obs.profiler_captures") \
            == before + 1
        # sever the loopback leg: now only the cluster token admits
        monkeypatch.setattr(app_mod, "_is_loopback", lambda req: False)
        res = await client.post("/debug/trace?seconds=0.05&name=gate")
        assert res.status == 403
        fabric = client.app[app_mod._FABRIC]
        # the legacy one-Game wrap runs heartbeatless and never minted
        # a key; mint one the way the first fabric beat would — the
        # GATE (not key distribution, covered in test_obs_cluster) is
        # what this test pins
        await fabric._ensure_cluster_key()
        token = fabric.cluster_token()
        assert token, "fabric should mint a cluster token"
        res = await client.post(
            "/debug/trace?seconds=0.05&name=gate",
            headers={"X-Cluster-Auth": token})
        assert res.status == 200
    finally:
        await client.close()


@pytest.mark.asyncio
async def test_debug_trace_single_flight(tiny_cfg):
    import asyncio

    client = await _make_client(tiny_cfg)
    try:
        first = asyncio.create_task(
            client.post("/debug/trace?seconds=0.4&name=sf"))
        await asyncio.sleep(0.1)   # let the first capture start
        second = await client.post("/debug/trace?seconds=0.1&name=sf")
        assert second.status == 409
        assert (await first).status == 200
    finally:
        await client.close()


# -- cost-model drift gate (satellite: CI/tooling) --------------------------

def test_cost_model_artifact_matches_regeneration(tmp_path):
    """Regenerate data/cost_model.json via --emit-cost-model (in
    process — pure eval_shape tracing, no weights) and compare to the
    committed artifact: a model/config change that shifts the analytic
    cost MUST re-emit the artifact in the same PR (the fault-point/
    env-flag lint spirit, applied to the cost model)."""
    from tools.profile_unet import emit_cost_model

    out = tmp_path / "cost_model.json"
    regenerated = emit_cost_model(str(out))
    committed_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "data", "cost_model.json")
    with open(committed_path) as f:
        committed = json.load(f)
    assert regenerated == committed, (
        "data/cost_model.json drifted from the configs: rerun "
        "`python tools/profile_unet.py --platform cpu "
        "--emit-cost-model data/cost_model.json` and commit the result")


def test_trace_cost_counts_scan_trip_and_bytes():
    """trace_cost multiplies scan bodies by their trip count and the
    byte proxy counts operand+result buffers."""
    def body(c, _):
        return c @ c, None

    def scanned(x):
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    x = jnp.ones((8, 8), jnp.float32)
    flops, hbm = costmodel.trace_cost(scanned, x)
    assert flops == pytest.approx(5 * 2 * 8 * 8 * 8)
    # per matmul: 2 operands + 1 result, 8x8 f32 each
    assert hbm == pytest.approx(5 * 3 * 8 * 8 * 4)


def test_params_count_and_bytes():
    tree = {"a": np.zeros((4, 4), np.float32),
            "b": {"c": np.zeros((10,), np.int8)}}
    assert costmodel.params_count(tree) == 26
    assert costmodel.params_bytes(tree) == 4 * 4 * 4 + 10
