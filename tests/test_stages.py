"""Stage-disaggregated serving (serving/stages.py): parity + scheduling.

The acceptance bars from the stage-graph refactor (ISSUE 6), in test
form:

- **solo bit-parity** — a request through the staged encode/denoise/
  decode graph produces BYTE-identical images to the monolithic
  dispatch for the same seed/prompt, on both the SD1.5 and SDXL-shaped
  test configs (the kill switch flips the SAME pipeline object between
  paths, so params/tokenizer/jit inputs are held constant);
- **continuous batching is real** — a request submitted mid-denoise of
  another is admitted into a free slot at a step boundary BEFORE that
  denoise finishes (slot-step accounting proves overlap), both outputs
  stay bit-correct, and the denoise step function compiles exactly once
  for the whole mixed admission/retirement history;
- **step-granular deadlines** — an expired request frees its slot at
  the next boundary (DeadlineExceeded) without perturbing a neighbor's
  trajectory;
- **containment** — a step failure fails the waiting callers instead of
  hanging them, and stop() fails pending work with QueueStopped; both
  leave the server restartable.

The module deliberately stays OUT of the ``fast`` tier (it compiles
three pipeline-sized jits); it runs in the default tier-1 sweep like
test_spec_decode (tests/conftest.py tier map).
"""

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.config import test_config as _tiny_config
from cassmantle_tpu.config import test_sdxl_config as _tiny_sdxl_config
from cassmantle_tpu.ops.samplers import make_sampler, make_slot_sampler
from cassmantle_tpu.serving.queue import DeadlineExceeded, QueueStopped
from cassmantle_tpu.serving.supervisor import ServingSupervisor

KILL = "CASSMANTLE_NO_STAGED_SERVING"


def staged_test_config():
    base = _tiny_config()
    return base.replace(serving=dataclasses.replace(
        base.serving, staged_serving=True, denoise_slots=3))


@pytest.fixture(scope="module")
def sd_pipe():
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    pipe = Text2ImagePipeline(staged_test_config())
    pipe.supervisor = ServingSupervisor()
    yield pipe
    if pipe._staged is not None:
        pipe._staged.stop()


@pytest.fixture(autouse=True)
def _clear_hook(sd_pipe):
    yield
    if sd_pipe._staged is not None:
        sd_pipe._staged._on_step = None


# -- slot sampler unit parity (no UNet: cheap, covers every kind) ------------

def _toy_denoise(x, t):
    tt = jnp.asarray(t, jnp.float32)
    if tt.ndim:
        tt = tt.reshape((-1,) + (1,) * (x.ndim - 1))
    return 0.003 * x * (tt + 1.0) - 0.01 * x


@pytest.mark.parametrize("kind", ["ddim", "euler", "dpmpp_2m"])
def test_slot_sampler_matches_scan_bitwise(kind):
    """make_slot_sampler replays make_sampler's scan body verbatim: a
    solo trajectory stepped one JITTED slot-step at a time is
    bit-identical to the monolithic lax.scan, for every stageable
    sampler kind. The step must run under jit exactly as the server
    dispatches it (StagedImageServer._step): XLA then fuses the step
    body the same way it fuses the scan body — eager per-op dispatch
    would skip those fusions and drift in the last ulp."""
    num_steps = 5
    lat = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 4, 4, 2)),
        jnp.float32)
    ref = make_sampler(kind, num_steps)(_toy_denoise, lat)
    prepare, slot_step, n = make_slot_sampler(kind, num_steps)
    assert n == num_steps
    step = jax.jit(
        lambda x, aux, idx: slot_step(_toy_denoise, x, aux, idx))
    x, aux = prepare(lat)
    for i in range(num_steps):
        x, aux = step(x, aux, jnp.full((1,), i, jnp.int32))
    assert np.array_equal(np.asarray(ref), np.asarray(x)), kind


def test_slot_sampler_rejects_stochastic_eta():
    with pytest.raises(ValueError, match="eta"):
        make_slot_sampler("ddim", 4, eta=0.3)


# -- routing decision --------------------------------------------------------

def test_staged_enabled_gating(monkeypatch):
    """The per-call routing decision: on for the supported configs, off
    for everything the slot stepper cannot replay exactly, off under
    the kill switch."""
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    monkeypatch.delenv(KILL, raising=False)
    cfg = staged_test_config()

    def ns(cfg, mesh=None):
        return SimpleNamespace(cfg=cfg, mesh=mesh)

    enabled = Text2ImagePipeline._staged_enabled
    assert enabled(ns(cfg))
    assert not enabled(ns(_tiny_config()))          # knob off
    assert not enabled(ns(cfg, mesh=object()))     # meshed serving
    for sampler in (
        dataclasses.replace(cfg.sampler, eta=0.5),
        dataclasses.replace(cfg.sampler, kind="nonexistent"),
    ):
        assert not enabled(ns(cfg.replace(sampler=sampler)))
    monkeypatch.setenv(KILL, "1")
    assert not enabled(ns(cfg))                    # kill switch


# -- solo bit-parity ---------------------------------------------------------

def _mono_ref(monkeypatch, pipe, prompts, seed):
    """The monolithic output of the SAME pipeline object (kill switch
    routes generate() through the proven whole-jit dispatch)."""
    monkeypatch.setenv(KILL, "1")
    try:
        return pipe.generate(prompts, seed=seed)
    finally:
        monkeypatch.delenv(KILL, raising=False)


def test_solo_bit_parity_sd15(sd_pipe, monkeypatch):
    prompt = ["a lighthouse over a stormy sea"]
    ref = _mono_ref(monkeypatch, sd_pipe, prompt, seed=7)
    out = sd_pipe.generate(prompt, seed=7)
    assert out.dtype == np.uint8 and out.shape == ref.shape
    assert np.array_equal(ref, out), "staged SD1.5 output diverged"
    # a second seed exercises a fresh latent draw through the SAME
    # compiled step function
    ref2 = _mono_ref(monkeypatch, sd_pipe, prompt, seed=8)
    out2 = sd_pipe.generate(prompt, seed=8)
    assert np.array_equal(ref2, out2)
    assert not np.array_equal(ref, ref2)  # the seed actually matters


def test_multi_prompt_request_bit_parity(sd_pipe, monkeypatch):
    """A B=2 request splits into two denoise slots but draws its
    latents as ONE (2, ...) normal draw, exactly like the monolithic
    batch — rows must come back identical and in order."""
    prompts = ["a caravan crossing silver dunes", "an orchard at night"]
    ref = _mono_ref(monkeypatch, sd_pipe, prompts, seed=11)
    out = sd_pipe.generate(prompts, seed=11)
    assert np.array_equal(ref, out)


def test_solo_bit_parity_sdxl(monkeypatch):
    """Same parity bar for the SDXL shape: dual-tower conditioning +
    micro-conds ride the cond dict as add/uadd rows."""
    from cassmantle_tpu.serving.sdxl import SDXLPipeline

    base = _tiny_sdxl_config()
    cfg = base.replace(serving=dataclasses.replace(
        base.serving, staged_serving=True, denoise_slots=2))
    pipe = SDXLPipeline(cfg)
    try:
        prompt = ["a stained glass window of two moons"]
        ref = _mono_ref(monkeypatch, pipe, prompt, seed=5)
        out = pipe.generate(prompt, seed=5)
        assert np.array_equal(ref, out), "staged SDXL output diverged"
    finally:
        if pipe._staged is not None:
            pipe._staged.stop()


# -- continuous batching: mid-flight admission -------------------------------

def test_mid_flight_admission_and_compile_once(sd_pipe, monkeypatch):
    """The tentpole property: request B, submitted while request A is
    mid-denoise, joins at a step boundary BEFORE A finishes. The
    step-loop hook holds the boundary after A's second step until B's
    encoded conditioning reaches the admission queue, so the overlap is
    deterministic, then slot-step accounting proves both requests
    actually shared step dispatches. Both outputs stay bit-identical to
    their monolithic references, and the jitted step function has
    compiled exactly ONCE across the whole admission/retirement
    history."""
    prompt_a = ["a night train between cities"]
    prompt_b = ["a watercolor harbor at dawn"]
    ref_a = _mono_ref(monkeypatch, sd_pipe, prompt_a, seed=21)
    ref_b = _mono_ref(monkeypatch, sd_pipe, prompt_b, seed=22)

    srv = sd_pipe._staged_server()
    base = dict(srv.stats)
    num_steps = srv.num_steps
    snaps = []

    def hook(s):
        snaps.append((s.stats["steps"] - base["steps"],
                      s.stats["admissions"] - base["admissions"]))
        if (s.stats["admissions"] - base["admissions"] == 1
                and s.stats["steps"] - base["steps"] >= 2):
            deadline = time.monotonic() + 30.0
            while (s._admit_q.empty() and not s._pend
                    and time.monotonic() < deadline
                    and not s._stop_evt.is_set()):
                time.sleep(0.002)

    srv._on_step = hook
    with ThreadPoolExecutor(max_workers=2) as ex:
        fa = ex.submit(sd_pipe.generate, prompt_a, 21)
        # B arrives only once A is admitted (denoise in flight)
        deadline = time.monotonic() + 30.0
        while (srv.stats["admissions"] - base["admissions"] < 1
                and time.monotonic() < deadline):
            time.sleep(0.002)
        fb = ex.submit(sd_pipe.generate, prompt_b, 22)
        out_a = fa.result(timeout=120)
        out_b = fb.result(timeout=120)
    srv._on_step = None

    assert np.array_equal(ref_a, out_a), "neighbor admission perturbed A"
    assert np.array_equal(ref_b, out_b), "mid-flight admission broke B"
    # B was admitted mid-denoise of A: at some observed boundary the
    # second admission had happened while A (admitted at step 0) still
    # had steps to run
    b_admit_steps = [s for s, adm in snaps if adm == 2]
    assert b_admit_steps, "B was never admitted while observable"
    assert min(b_admit_steps) < num_steps, (
        "B only joined after A's denoise completed — that is a rename, "
        "not continuous batching")
    # overlap in the slot tensor: some steps advanced BOTH slots
    d_steps = srv.stats["steps"] - base["steps"]
    d_slot_steps = srv.stats["slot_steps"] - base["slot_steps"]
    assert d_slot_steps > d_steps, "no step ever ran two live slots"
    assert d_slot_steps == 2 * num_steps  # every request got its steps
    # the step function compiles once per occupancy-width bucket, never
    # per admission/retirement: this module has only ever driven widths
    # 1 and 2, across MANY admissions
    cache_after = srv._step._cache_size()
    assert cache_after <= 2, "step recompiled beyond the width buckets"
    # ...and another full request (width 1, already compiled) plus the
    # admissions it implies grow the cache by nothing
    sd_pipe.generate(prompt_a, seed=23)
    assert srv._step._cache_size() == cache_after
    # the jit compile-count sentinel pins the same steady-state claim
    # across the WHOLE stage graph (encode/init/admit/step/take/
    # decode), not just the step cache: admissions in warmed width
    # buckets compile nothing anywhere
    from cassmantle_tpu.utils import jit_sentinel

    with jit_sentinel.no_new_compiles():
        sd_pipe.generate(prompt_b, seed=24)
    assert srv._step._cache_size() == cache_after


# -- deadlines at step granularity -------------------------------------------

def test_deadline_expiry_frees_slot_without_corrupting_neighbor(
        sd_pipe, monkeypatch):
    prompt_a = ["an art deco skyline"]
    prompt_b = ["a vaporwave fountain"]
    ref_a = _mono_ref(monkeypatch, sd_pipe, prompt_a, seed=31)

    srv = sd_pipe._staged_server()
    base = dict(srv.stats)
    state = {}

    def hook(s):
        # once both requests occupy slots, stall ONE boundary long
        # enough to blow B's deadline; the next tick preempts it
        if (s.stats["admissions"] - base["admissions"] >= 2
                and "slept" not in state):
            state["slept"] = True
            time.sleep(0.7)

    srv._on_step = hook
    with ThreadPoolExecutor(max_workers=2) as ex:
        fa = ex.submit(sd_pipe.generate, prompt_a, 31)
        fb = ex.submit(lambda: sd_pipe.generate(prompt_b, 32,
                                                deadline_s=0.5))
        out_a = fa.result(timeout=120)
        with pytest.raises(DeadlineExceeded):
            fb.result(timeout=120)
    srv._on_step = None

    assert srv.stats["preemptions"] - base["preemptions"] >= 1
    assert np.array_equal(ref_a, out_a), (
        "preempting a neighbor's slot perturbed a live trajectory")
    # the freed slot is reusable: a follow-up request completes
    assert sd_pipe.generate(prompt_b, seed=33).shape == out_a.shape


# -- kill switch & fallback --------------------------------------------------

def test_kill_switch_routes_monolithic(sd_pipe, monkeypatch):
    srv = sd_pipe._staged_server()
    before = dict(srv.stats)
    monkeypatch.setenv(KILL, "1")
    out = sd_pipe.generate(["a quiet glass valley"], seed=41)
    assert out.dtype == np.uint8
    # no staged admission happened: the monolithic jit served it
    assert srv.stats == before


# -- observability -----------------------------------------------------------

def test_stage_metrics_events_and_supervisor_health(sd_pipe, monkeypatch):
    from cassmantle_tpu.obs.recorder import flight_recorder
    from cassmantle_tpu.utils.logging import metrics

    sd_pipe.generate(["a velvet comet"], seed=51)
    snap = metrics.snapshot()
    assert snap["counters"].get("stage.denoise.admissions", 0) >= 1
    assert "stage.denoise.queue_wait_s" in snap["timings"]
    assert "stage.denoise.service_s" in snap["timings"]
    # the per-stage BatchingQueues report under their stage names
    assert "stage.encode.batch_size" in snap["timings"]
    assert "stage.decode.queue_wait_s" in snap["timings"]
    assert snap["gauges"]["stage.denoise.slot_occupancy"] <= 1.0
    kinds = {e["kind"] for e in flight_recorder.tail(200)}
    assert {"stage.admit", "stage.retire"} <= kinds
    # per-stage progress fused into the one supervisor /readyz feeds
    health = sd_pipe.supervisor.stage_health()
    assert {"encode", "denoise", "decode"} <= set(health)
    status = sd_pipe.supervisor.status()
    assert set(status["stages"]) >= {"encode", "denoise", "decode"}


# -- containment & lifecycle -------------------------------------------------

def test_step_failure_fails_caller_not_hangs(sd_pipe):
    srv = sd_pipe._staged_server()
    orig = srv._step

    def boom(*a, **k):
        raise RuntimeError("injected step failure")

    srv._step = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            sd_pipe.generate(["a broken loom"], seed=61)
    finally:
        srv._step = orig
    # the loop survived and the slot state reset: next request is clean
    out = sd_pipe.generate(["a mended loom"], seed=62)
    assert out.dtype == np.uint8


def test_stop_fails_pending_and_server_restarts(sd_pipe):
    srv = sd_pipe._staged_server()
    hold = threading.Event()

    def hook(s):
        while not hold.is_set() and not s._stop_evt.is_set():
            time.sleep(0.002)

    srv._on_step = hook
    with ThreadPoolExecutor(max_workers=1) as ex:
        fut = ex.submit(sd_pipe.generate, ["an unfinished bridge"], 71)
        deadline = time.monotonic() + 30.0
        while (not srv._pend and srv._admit_q.empty()
                and not srv._alive.any()
                and time.monotonic() < deadline):
            time.sleep(0.002)
        srv.stop()
        hold.set()
        with pytest.raises(QueueStopped):
            fut.result(timeout=60)
    srv._on_step = None
    # stopped is not wedged: the next generate restarts the stage graph
    out = sd_pipe.generate(["a rebuilt bridge"], seed=72)
    assert out.dtype == np.uint8
