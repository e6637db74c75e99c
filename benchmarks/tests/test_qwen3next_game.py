"""The ``qwen3next_game`` configuration: its file against the catalog row it
was cut from and against the program, its rehearsal with faults planted,
its image trajectory against the program's sampler, its FLOP count, and
the two readers its cell adds, on tables and counters written by hand."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import compare as cmp
from benchmarks.harness import flops, stack
from benchmarks.harness.manifest import Cell, load_manifest
from benchmarks.harness.runner import execute
from benchmarks.readers import moe_roofline, trace_scope_pct
from benchmarks.references import qwen3_next as plain
from benchmarks.tests.test_cells import _broken

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
V5E = "TPU v5 lite"


@pytest.fixture()
def cell() -> Cell:
    return Cell(load_manifest(), "qwen3next_rollover")


# -- the file -----------------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_is_reduced(cell):
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["source_url"] == cell.config["source"])
    entry = next(c for c in load_manifest()["configs"]
                 if c["name"] == "qwen3next_game")
    differs = sorted(k for k, v in row["config"].items()
                     if cell.config.get(k, "absent") != v)
    assert differs == sorted(entry["reduced"]) == sorted(
        cell.config["reduced"])
    assert cell.config["published"] == {k: row["config"][k] for k in differs}
    lm = cell.config["sizes"]["qwen3_next"]
    # the sizes that run: the published ones, the router at its published
    # width, and the chip's share
    assert {k: lm[k] for k in row["config"] if k in lm and k not in differs} \
        == {k: v for k, v in row["config"].items()
            if k in lm and k not in differs}
    assert (lm["num_experts"], lm["experts_held"], lm["first_expert"]) == (
        512, cell.config["num_experts"], 0)
    assert lm["num_hidden_layers"] == cell.config["num_hidden_layers"] == 8
    assert lm["vocab_size"] == cell.config["vocab_size"] == 151936 // 4
    assert set(cell.config["limits"]) == set(cell.config["limits_why"])


def test_the_file_is_the_program_at_the_cells_size(cell):
    cfg = stack.framework_config(cell.config, False)
    running = stack.program_sizes(cfg, cell.config)
    assert stack.check_sizes(cell.config["sizes"], running) == []
    names = cmp.named(cell.config, cell.config["sizes"])
    assert names["lm_logits"] is plain.qwen3next_logits
    assert names["trajectory"] is plain.consistency_trajectory


def test_lm_flops_book_the_routed_experts_not_the_held_ones(cell):
    """Per token at the cell's size: the mixers, router, shared expert
    and head, plus 10 x 128 / 512 = 2.5 experts a layer; evaluating all
    128 held experts would book 51 times the experts' share."""
    from cassmantle_tpu.models.qwen3_next import Qwen3NextLM

    cfg = stack.framework_config(cell.config, False)
    names = cmp.named(cell.config, cell.config["sizes"])
    tree = jax.eval_shape(Qwen3NextLM(cfg.models.qwen3_next).init,
                          jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), "int32"))
    n = 30 + 96
    total = flops.lm_flops({"lm": tree}, names, 30, 96)
    expert = 2 * 3 * 2048 * 512
    linear = 2 * 2048 * (12288 + 64) + 2 * 4096 * 2048 \
        + 2 * 4 * 8192 + 6 * 32 * 128 * 128
    full = 2 * 2048 * (8192 + 1024) + 2 * 4096 * 2048
    block = 2 * 2048 * 512 + 2.5 * expert + expert + 2 * 2048
    per_token = 6 * linear + 2 * full + 8 * block + 2 * 2048 * 37984
    attention = 2 * 4 * 16 * n * n * 256
    assert total == pytest.approx(n * per_token + attention, rel=1e-12)
    assert total < 0.12e12  # 0.43 B parameters a token, not 3.67 B


# -- the rehearsal, with faults planted ---------------------------------------

def without_conv_window(params, ids, positions, sz):
    def linear(p, x, pos, *, d):
        return plain.layer(p, x, pos, d, False, conv_window=False)

    return plain.qwen3next_logits(params, ids, positions, sz, linear)


def without_decay(params, ids, positions, sz):
    def linear(p, x, pos, *, d):
        return plain.layer(p, x, pos, d, False, decay=False)

    return plain.qwen3next_logits(params, ids, positions, sz, linear)


@pytest.mark.parametrize("fault, check", [
    ("token", "lm_logit_gap"), ("image", "image_mean_abs_diff"),
    ("without_conv_window", "lm_logit_gap"),
    ("without_decay", "lm_logit_gap")])
def test_a_fault_in_the_program_or_a_hole_in_the_reference_is_not_correct(
        monkeypatch, cell, fault, check):
    """One served token or image altered where it is produced, or the
    reference without the convolution's window or without the decay: the
    comparison that decides ``correct`` sees each."""
    if fault in ("token", "image"):
        _broken(monkeypatch, fault)
    else:
        cell.config = dict(cell.config, prompt_lm=dict(
            cell.config["prompt_lm"],
            reference=f"benchmarks.tests.test_qwen3next_game:{fault}"))
    line = execute(cell, 31, 2.0, False, True, time.perf_counter())
    got = line["checks"][check]
    assert line["correct"] is False and got["value"] > got["limit"], got


def test_the_control_in_fp8_is_not_correct(cell):
    """The reference in fp8 in the program's place puts other tokens
    first: the cell's own limit sees it (at the tiny size fp8 swaps
    experts at every other position)."""
    kept = {}
    real = cmp.compare

    def keeping(book, window, trees, sizes, names, plan, seed, **kw):
        kept.update(args=(book, window, trees, sizes, names, plan, seed))
        return real(book, window, trees, sizes, names, plan, seed, **kw)

    cmp.compare = keeping
    try:
        line = execute(cell, 32, 2.0, False, True, time.perf_counter())
    finally:
        cmp.compare = real
    assert line["correct"] is True, line["checks"]
    _, _, trees, sizes, names, _, _ = kept["args"]
    control = cmp.Reference(cmp.reference_trees(trees, sizes, names), sizes,
                            names, "fp8")
    values = real(*kept["args"], served=control)
    assert cmp.verdict(values, cell.config["limits"],
                       cmp.required_numbers(cell.config, cell.traffic)
                       )[0] is False, values
    assert values["lm_logit_gap"] > cell.config["limits"]["lm_logit_gap"]


# -- the image trajectory -----------------------------------------------------

@pytest.mark.parametrize("steps, teacher", [(4, 50), (2, 50), (8, 25)])
def test_consistency_trajectory_is_the_programs_sampler(steps, teacher):
    from cassmantle_tpu.ops.samplers import (
        ConsistencySchedule,
        consistency_sample,
    )

    def eps_model(x, t):  # stands for the guided UNet
        return jnp.tanh(x) * 0.7 + jnp.cos(t.astype(jnp.float32) / 100.0)

    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 8, 4))
    got = consistency_sample(
        lambda x, t: eps_model(x, t), x,
        ConsistencySchedule.create(steps, teacher))
    want = plain.consistency_trajectory(
        lambda x, t: eps_model(x, jnp.asarray(t)), x,
        {"consistency": True, "num_steps": steps,
         "consistency_teacher_steps": teacher})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_consistency_trajectory_refuses_another_sampler():
    with pytest.raises(SystemExit, match="without consistency"):
        plain.consistency_trajectory(lambda x, t: x, jnp.zeros((1, 2, 2, 4)),
                                     {"consistency": False, "num_steps": 4})


# -- the readers --------------------------------------------------------------

def row(name, scope, seconds, calls=1):
    return {"name": name, "hlo": "", "scope": scope, "seconds": seconds,
            "calls": calls}


STEP = "jit(lm_decode)/while/body/closed_call/lm_decode_step/" \
       "Qwen3NextLM.decode_step/"
TABLE = {"busy_s": 1.2, "window_s": 1.5, "instructions": [
    row("fusion", STEP + "layer_0/gated_delta/mixer/in_proj_qkvz/dot:", 0.30),
    row("fusion", STEP + "layer_0/moe/moe_experts/moe._walk/while/body/"
        "dot_general:", 0.09, 900),
    row("fusion", "jit(lm_decode)/lm_prefill/Qwen3NextLM.prefill/layer_0/"
        "moe/moe_experts/tef,efd->td/dot_general:", 0.03, 40),
    row("fusion", STEP + "layer_3/gated_attn/mixer/o_proj/dot_general:", 0.2),
    row("fusion", "jit(t2i_sample)/denoise_scan/while/body/denoise_step/"
        "UNet/mid_attn/block_0/ff/proj/dot_general:", 0.4),
    row("copy", "", 0.01)]}


@pytest.mark.parametrize("scope, want", [
    ("lm_decode_step", 100 * 0.59 / 1.2), ("moe_experts", 100 * 0.12 / 1.2),
    ("gated_delta", 100 * 0.30 / 1.2),
    ("moe", 100 * 0.12 / 1.2),         # a whole path component, not a prefix
    ("moe_router", None), ("decode_step", None)])
def test_trace_scope_pct_sums_the_instructions_under_a_scope(scope, want):
    got = trace_scope_pct.read({"trace": TABLE}, {"scope": scope})
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("ctx", [
    {}, {"trace": {}}, {"trace": dict(TABLE, busy_s=0.0)},
    {"trace": dict(TABLE, instructions=[])}],
    ids=["no_trace", "empty_trace", "never_busy", "the_parent_s_program"])
def test_trace_scope_pct_returns_nothing_where_nothing_is_to_read(ctx):
    assert trace_scope_pct.read(ctx, {"scope": "moe_experts"}) is None


class Counters:
    def __init__(self, **values):
        self.values = values

    def counter(self, name):
        return self.values.get(name, 0.0)


ROOFLINE_ARGS = {
    "scope": "moe_experts",
    "floor": "benchmarks.references.qwen3_next:moe_floor_s",
    "counters": {"experts_touched": "moe.experts_touched",
                 "assignments_held": "moe.assignments_held"}}
LM_SIZES = {"hidden_size": 2048, "moe_intermediate_size": 512,
            "dtype": "bfloat16"}


def roofline_ctx(touched, held, window_s=10.0, trace=TABLE):
    return {"trace": trace, "window_s": window_s, "device_kind": V5E,
            "names": {"lm_sizes": LM_SIZES},
            "window": Counters(**{"moe.experts_touched": touched,
                                  "moe.assignments_held": held})}


def test_moe_floor_is_the_larger_of_bytes_and_flops():
    expert_bytes = 3 * 2048 * 512 * 2
    assert expert_bytes == 6291456
    # decode: an expert read for one row, bound by the memory
    assert plain.moe_floor_s(LM_SIZES, V5E, 1000, 1000) == pytest.approx(
        1000 * expert_bytes / 819e9)
    # prefill of many rows an expert: bound by the MXU
    assert plain.moe_floor_s(LM_SIZES, V5E, 128, 128 * 1000) == pytest.approx(
        128 * 1000 * expert_bytes / 197e12)


def test_moe_roofline_brings_counters_and_trace_to_one_span():
    """80,000 experts touched in a 10 s window: 0.6145 s of reading at the
    memory's peak, 6.145% of the window; 0.12 s under ``moe_experts`` in a
    1.5 s slice, 8% of it."""
    got = moe_roofline.read(roofline_ctx(80000, 80000), ROOFLINE_ARGS)
    floor_share = 80000 * 6291456 / 819e9 / 10.0
    assert got == pytest.approx(100 * floor_share / (0.12 / 1.5))
    assert 76 < got < 77


@pytest.mark.parametrize("seed", range(5))
def test_moe_roofline_cannot_pass_100_for_a_program_that_reads_what_it_touched(
        seed):
    """By construction: a program that takes, for every expert layer's
    call, at least the time the memory needs for the experts the counters
    say were touched (more where it reads all it holds, or walks slowly)
    reads at most 100, whatever the mix of calls."""
    rng = np.random.RandomState(seed)
    calls = rng.randint(1, 129, size=400)          # experts touched a call
    slack = 1.0 + rng.rand(400) * 3.0              # this program's excess
    seconds = calls * 6291456 / 819e9 * slack
    trace = {"busy_s": 1.4, "window_s": 1.5, "instructions": [
        row("fusion", "jit(lm_decode)/x/moe_experts/dot_general:", s)
        for s in seconds]}
    ctx = roofline_ctx(float(calls.sum()), float(calls.sum()),
                       window_s=1.5, trace=trace)
    got = moe_roofline.read(ctx, ROOFLINE_ARGS)
    assert 25.0 <= got <= 100.0


@pytest.mark.parametrize("ctx", [
    {"window": Counters(), "window_s": 10.0},
    roofline_ctx(0, 0),
    roofline_ctx(5, 5, trace=dict(TABLE, instructions=[])),
    roofline_ctx(5, 5, trace={})],
    ids=["no_trace", "nothing_counted", "no_such_scope", "empty_trace"])
def test_moe_roofline_returns_nothing_where_nothing_is_to_read(ctx):
    assert moe_roofline.read(ctx, ROOFLINE_ARGS) is None
