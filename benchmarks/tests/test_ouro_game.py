"""The ``ouro_game`` configuration: its file against the catalog row it was
taken from, uncut, and against the program; its FLOP count, four passes
included; its rehearsal with faults planted in the loop; and the decode
step's roofline reader on traces of one orbit cut at different phases."""

import json
import time

import jax
import numpy as np
import pytest

from benchmarks.harness import compare as cmp
from benchmarks.harness import flops, stack, trace
from benchmarks.harness.manifest import Cell, load_manifest
from benchmarks.harness.runner import execute
from benchmarks.readers import step_roofline
from benchmarks.references import ouro as plain
from benchmarks.references import qwen3_next as sibling
from benchmarks.tests.test_cells import _broken
from benchmarks.tests.test_qwen3next_game import CATALOG, V5E, Counters


@pytest.fixture()
def cell() -> Cell:
    return Cell(load_manifest(), "ouro_rollover")


# -- the file -----------------------------------------------------------------

def test_the_file_holds_the_published_config_uncut(cell):
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    published = next(r for r in rows
                     if r["source_url"] == cell.config["source"])["config"]
    entry = next(c for c in load_manifest()["configs"]
                 if c["name"] == "ouro_game")
    assert {k: cell.config.get(k, "absent") for k in published} == published
    assert entry["reduced"] == cell.config["reduced"] == []
    assert cell.config["published"] == {}
    lm = cell.config["sizes"]["ouro"]
    same = [k for k in published if k in lm]
    assert {k: lm[k] for k in same} == {k: published[k] for k in same}
    assert len(same) >= 16
    assert (lm["num_hidden_layers"], lm["total_ut_steps"],
            lm["vocab_size"]) == (48, 4, 49152)
    assert cell.config["deployment"]["chips_sharing_a_layer"] == 1
    assert set(cell.config["limits"]) == set(cell.config["limits_why"])


def test_the_file_is_the_program_at_the_cells_size(cell):
    cfg = stack.framework_config(cell.config, False)
    running = stack.program_sizes(cfg, cell.config)
    assert stack.check_sizes(cell.config["sizes"], running) == []
    names = cmp.named(cell.config, cell.config["sizes"])
    assert names["lm_logits"] is plain.ouro_logits
    assert names["trajectory"] is sibling.consistency_trajectory
    assert cell.config["overrides"] == {
        "serving": {"max_queue_delay_ms": 0.0}}
    # the image side is qwen3next_game's, group for group
    other = Cell(load_manifest(), "qwen3next_rollover").config["sizes"]
    for group in ("clip_text", "unet", "vae", "sampler", "minilm"):
        assert cell.config["sizes"][group] == other[group], group


def test_lm_flops_book_the_stack_once_a_pass(cell):
    """Per token at the cell's size: four passes of the stack (q, k, v, o
    and the SwiGLU, the exit gate after each), the head once; attention
    over n keys a query, every pass and layer."""
    from cassmantle_tpu.models.ouro import OuroLM

    cfg = stack.framework_config(cell.config, False)
    names = cmp.named(cell.config, cell.config["sizes"])
    tree = jax.eval_shape(OuroLM(cfg.models.ouro).init,
                          jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), "int32"))
    n = 30 + 96
    total = flops.lm_flops({"lm": tree}, names, 30, 96)
    layer = 2 * 2048 * (4 * 2048 + 3 * 5632)
    per_token = 4 * (48 * layer + 2 * 2048) + 2 * 2048 * 49152
    attention = 4 * 48 * 4 * 16 * n * n * 128
    assert total == pytest.approx(n * per_token + attention, rel=1e-12)
    assert per_token == pytest.approx(2 * (4 * 2.4666e9 + 0.1007e9),
                                      rel=1e-3)


# -- the rehearsal, with faults planted ---------------------------------------

@pytest.mark.parametrize("fault", [
    "token", "a_pass_dropped", "two_passes_share_a_cache"])
def test_a_fault_in_the_program_is_not_correct(monkeypatch, cell, fault):
    """A served token altered where it is produced, a pass taken out of the
    loop, or the first two passes writing and reading one cache entry: the
    comparison that decides ``correct`` sees each."""
    from cassmantle_tpu.models.ouro import OuroLM
    from tests.test_ouro import faulty_loop

    if fault == "token":
        _broken(monkeypatch, fault)
    else:
        monkeypatch.setattr(OuroLM, "_loop", faulty_loop(
            "shared_cache" if fault == "two_passes_share_a_cache" else fault))
    line = execute(cell, 41, 4.0, False, True, time.perf_counter())
    got = line["checks"]["lm_logit_gap"]
    assert line["correct"] is False and got["value"] > got["limit"], got


def test_the_rehearsal_is_correct_and_counts_the_passes(cell):
    from benchmarks.harness import runner

    kept = {}
    real = runner.run_window

    def keeping(*args, **kw):
        res = real(*args, **kw)
        kept["window"] = res["window"]
        return res

    runner.run_window = keeping
    try:
        line = execute(cell, 42, 4.0, False, True, time.perf_counter())
    finally:
        runner.run_window = real
    assert line["correct"] is True, line["checks"]
    window = kept["window"]
    pass_steps = window.counter("ouro.pass_steps")
    # every decode of the window is 8 steps of 4 passes, at the tiny size
    assert pass_steps > 0 and pass_steps % (8 * 4) == 0
    assert window.counter("ouro.kv_slots") > pass_steps


# -- the decode step's roofline -----------------------------------------------

PROGRAM = "jit(lm_decode)"
STEP = PROGRAM + "/while/body/closed_call/lm_decode_step/OuroLM.decode_step/"
PASS = STEP + "OuroLM._loop/while/body/closed_call/OuroLM.one_pass/ouro_pass/"
SIZES = {"vocab_size": 49152, "hidden_size": 2048, "intermediate_size": 5632,
         "num_hidden_layers": 48, "num_attention_heads": 16, "head_dim": 128,
         "total_ut_steps": 4, "dtype": "bfloat16"}
ARGS = {"program": PROGRAM,
        "floor": "benchmarks.references.ouro:decode_floor_s",
        "counters": {"pass_steps": "ouro.pass_steps",
                     "kv_slots": "ouro.kv_slots"}}


def orbit_ops(t0, rng, step_ms=28.0, steps=96):
    """One orbit of the cell on the device: a prefill, ``steps`` decode
    steps (per pass two layer instructions and an unscoped wait on a
    prefetch, per step the head and the pick), then four images of a
    4-step sampler. (start_ns, duration_ns, name, hlo, scope)."""
    ops, t = [], t0

    def op(ms, name, hlo, scope):
        nonlocal t
        d = int(ms * rng.uniform(0.98, 1.02) * 1e6)
        ops.append((t, d, name, hlo, scope))
        t += d + 2000

    for p in range(4):
        op(3.0, "fusion", f"%fusion.1{p} = f32[4,64,2048] fusion()",
           PROGRAM + "/lm_prefill/OuroLM.prefill/ouro_pass/layer_0/mlp:")
    op(0.3, "fusion", "%fusion.2 = f32[4,49152] fusion()",
       PROGRAM + "/lm_prefill/OuroLM.prefill/OuroLM._logits/lm_head/add:")
    per_pass = (step_ms - 0.5) / 4
    for _ in range(steps):
        for _ in range(4):
            op(per_pass * 0.6, "fusion", "%fusion.3 = f32[4,1,2048] fusion()",
               PASS + "layer_0/mlp/down_proj/dot_general:")
            op(per_pass * 0.35, "fusion", "%fusion.4 = bf16[4,4,160,16,128]",
               PASS + "layer_0/self_attn/dynamic_update_slice:")
            op(per_pass * 0.05, "slice-done",
               "%slice-done.5 = bf16[1408,2048]", "")
        op(0.4, "fusion", "%slice_add_fusion.6 = f32[4,49152] fusion()",
           STEP + "OuroLM._logits/lm_head/add:")
        op(0.1, "fusion", "%fusion.7 = s32[4] fusion()",
           PROGRAM + "/while/body/closed_call/lm_decode_step/argmax:")
    for _ in range(4):
        for s in range(4):
            op(15.0, "convolution", f"%convolution.8{s} = bf16[2,64,64,320]",
               "jit(t2i_sample)/denoise_scan/while/body/denoise_step/unet:")
        op(20.0, "fusion", "%fusion.9 = f32[1,512,512,3]",
           "jit(t2i_sample)/vae_decode/conv:")
    return ops, t


def read_slice(offset_s, step_ms=28.0, seed=0):
    rng = np.random.RandomState(seed)
    ops, t = orbit_ops(0, rng, step_ms)
    more, _ = orbit_ops(t, rng, step_ms)
    start = int(offset_s * 1e9)
    table = trace.reduce_trace(ops + more, [], (start, start + int(1.5e9)))
    # the counters cover a window of whole orbits: 96 steps of 4 rows
    # attending prompts of about 30 slots and their tokens
    slots = sum(4 * (30 + i + 1) for i in range(96)) * 48 * 4
    ctx = {"trace": table, "names": {"lm_sizes": SIZES}, "device_kind": V5E,
           "window": Counters(**{"ouro.pass_steps": 96 * 4.0 * 3,
                                 "ouro.kv_slots": slots * 3.0})}
    return step_roofline.read(ctx, ARGS)


def test_the_share_is_a_steps_whatever_part_of_the_orbit_the_slice_holds():
    """A slice all decode steps, one that holds the prefill, one that holds
    the images and the next orbit's first steps: the same share within 1%,
    that of a step's floor (counters over the window) over a step's device
    time (28 ms here), whatever the slice's share of the LM."""
    orbit_s = 0.0135 + 96 * 0.028 + 4 * 0.080
    readings = [read_slice(offset) for offset in (0.4, orbit_s - 1.2,
                                                  orbit_s - 0.2)]
    floor_ms = 1e3 * plain.decode_floor_s(
        SIZES, V5E, 4, sum(4 * (30 + i + 1) for i in range(96)) * 48 * 4
        / 96)
    assert floor_ms == pytest.approx(25.0, abs=0.5)
    for got in readings:
        assert got == pytest.approx(100 * floor_ms / 28.0, rel=0.01)
    assert max(readings) / min(readings) < 1.01


def test_a_step_that_takes_the_floor_reads_100_and_no_more():
    floor_ms = 1e3 * plain.decode_floor_s(
        SIZES, V5E, 4, sum(4 * (30 + i + 1) for i in range(96)) * 48 * 4
        / 96)
    assert read_slice(0.4, step_ms=floor_ms) == pytest.approx(100, rel=0.01)
    assert read_slice(0.4, step_ms=floor_ms * 1.02) < 100


def test_nothing_is_read_without_the_trace_the_steps_or_the_counters():
    ctx = {"names": {"lm_sizes": SIZES}, "device_kind": V5E,
           "window": Counters(**{"ouro.pass_steps": 384.0,
                                 "ouro.kv_slots": 1e6})}
    assert step_roofline.read(ctx, ARGS) is None
    # a program that publishes no such counters (the parent's LMs)
    ops, _ = orbit_ops(0, np.random.RandomState(0))
    table = trace.reduce_trace(ops, [], (0, int(1.5e9)))
    assert step_roofline.read(dict(ctx, trace=table, window=Counters()),
                              ARGS) is None
    # a slice with no step's logits in it
    images = [op for op in ops if not op[4].startswith(PROGRAM)]
    table = trace.reduce_trace(images, [])
    assert step_roofline.read(dict(ctx, trace=table), ARGS) is None
