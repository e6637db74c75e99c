"""The ``lfm2_game`` configuration: its file against the catalog row it was
cut from and against the program, its FLOP count, its rehearsal with
faults planted, and the expert layers' floor at this expert size."""

import json
import time

import jax
import numpy as np
import pytest

from benchmarks.harness import compare as cmp
from benchmarks.harness import flops, stack
from benchmarks.harness.manifest import Cell, load_manifest
from benchmarks.harness.runner import execute
from benchmarks.readers import moe_roofline
from benchmarks.references import lfm2_moe as plain
from benchmarks.references import qwen3_next as sibling
from benchmarks.tests.test_cells import _broken
from benchmarks.tests.test_qwen3next_game import (
    CATALOG,
    V5E,
    Counters,
    row,
)


@pytest.fixture()
def cell() -> Cell:
    return Cell(load_manifest(), "lfm2_rollover")


# -- the file -----------------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_is_reduced(cell):
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    published = next(r for r in rows
                     if r["source_url"] == cell.config["source"])["config"]
    entry = next(c for c in load_manifest()["configs"]
                 if c["name"] == "lfm2_game")
    differs = sorted(k for k, v in published.items()
                     if cell.config.get(k, "absent") != v)
    assert differs == sorted(entry["reduced"]) == sorted(
        cell.config["reduced"]) == ["num_dense_layers", "num_hidden_layers"]
    assert cell.config["published"] == {k: published[k] for k in differs}
    lm = cell.config["sizes"]["lfm2_moe"]
    # the sizes that run: the published widths, every expert, every row
    same = [k for k in published if k in lm and k not in differs
            and k != "layer_types"]
    assert {k: lm[k] for k in same} == {k: published[k] for k in same}
    assert len(same) >= 14
    assert lm["rope_theta"] == published["rope_parameters"]["rope_theta"]
    assert (lm["num_experts"], lm["experts_held"], lm["first_expert"]) == (
        64, 64, 0)
    assert lm["vocab_size"] == 65536
    # depth only: published layers 0 and 2-9, one dense layer
    assert lm["layer_types"] == published["layer_types"][:1] \
        + published["layer_types"][2:10]
    assert lm["num_hidden_layers"] == cell.config["num_hidden_layers"] == 9
    assert lm["num_dense_layers"] == cell.config["num_dense_layers"] == 1
    assert cell.config["deployment"]["chips_sharing_a_layer"] == 1
    assert set(cell.config["limits"]) == set(cell.config["limits_why"])


def test_the_file_is_the_program_at_the_cells_size(cell):
    cfg = stack.framework_config(cell.config, False)
    running = stack.program_sizes(cfg, cell.config)
    assert stack.check_sizes(cell.config["sizes"], running) == []
    names = cmp.named(cell.config, cell.config["sizes"])
    assert names["lm_logits"] is plain.lfm2_logits
    assert names["trajectory"] is sibling.consistency_trajectory
    # the image side is qwen3next_game's, group for group
    other = Cell(load_manifest(), "qwen3next_rollover").config["sizes"]
    for group in ("clip_text", "unet", "vae", "sampler", "minilm"):
        assert cell.config["sizes"][group] == other[group], group


@pytest.mark.parametrize("name, factory", [
    ("lfm2_rollover", "lfm2_game_config"),
    ("qwen3next_rollover", "qwen3next_game_config")])
def test_the_files_override_is_a_prompt_queue_that_never_waits(name, factory):
    """Both LM-bound configurations: with the wait at 0 the queue forms no
    batch by a timer's chance (tests/test_queue.py), so a window's work is
    the program's choice, and nothing else differs from the factory's."""
    import dataclasses

    from cassmantle_tpu import config as program

    config = Cell(load_manifest(), name).config
    assert config["overrides"] == {"serving": {"max_queue_delay_ms": 0.0}}
    made = getattr(program, factory)()
    assert stack.framework_config(config, False) == made.replace(
        serving=dataclasses.replace(made.serving, max_queue_delay_ms=0.0))
    assert "never by a timer's chance" in config["overrides_why"]


def test_lm_flops_book_the_routed_experts_not_the_held_ones(cell):
    """Per token at the cell's size: the mixers, the dense MLP, the
    routers, the tied head, plus 4 experts a sparse layer; evaluating all
    64 held experts would book 16 times the experts' share."""
    from cassmantle_tpu.models.lfm2_moe import Lfm2MoeLM

    cfg = stack.framework_config(cell.config, False)
    names = cmp.named(cell.config, cell.config["sizes"])
    tree = jax.eval_shape(Lfm2MoeLM(cfg.models.lfm2_moe).init,
                          jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), "int32"))
    n = 30 + 96
    total = flops.lm_flops({"lm": tree}, names, 30, 96)
    expert = 2 * 3 * 2048 * 1536
    conv = 2 * 2048 * (6144 + 2048) + 2 * 2048 * 3
    full = 2 * 2048 * (2048 + 512 + 512 + 2048)
    sparse = 2 * 2048 * 64 + 4 * expert
    per_token = 7 * conv + 2 * full + 2 * 3 * 2048 * 11776 + 8 * sparse \
        + 2 * 2048 * 65536
    attention = 2 * 4 * 32 * n * n * 64
    assert total == pytest.approx(n * per_token + attention, rel=1e-12)
    assert per_token == pytest.approx(2 * 0.65e9, rel=0.01)
    assert total < 0.17e12  # 0.65 B parameters a token, not 5.18 B


# -- the rehearsal, with faults planted ---------------------------------------

def without_tap(params, ids, positions, sz):
    return plain.lfm2_logits(params, ids, positions, sz, without=("tap",))


@pytest.mark.parametrize("fault, check", [
    ("token", "lm_logit_gap"), ("image", "image_mean_abs_diff"),
    ("without_tap", "lm_logit_gap")])
def test_a_fault_in_the_program_or_a_hole_in_the_reference_is_not_correct(
        monkeypatch, cell, fault, check):
    """One served token or image altered where it is produced, or the
    reference without the convolution's oldest tap: the comparison that
    decides ``correct`` sees each (the tap read 3.7-5.6 on five seeds
    against the limit of 2.2). The selection bias is not planted here:
    at the tiny size, top-2 of 8, a bias of +-0.1 changes the choice at
    a few positions of a window only, and the reference without it read
    1.3-3.4 over five seeds, under the limit set at the served size on
    three; tests/test_lfm2_moe.py holds the bias against the float32
    tolerance instead, on the model and on the layer."""
    if fault in ("token", "image"):
        _broken(monkeypatch, fault)
    else:
        cell.config = dict(cell.config, prompt_lm=dict(
            cell.config["prompt_lm"],
            reference=f"benchmarks.tests.test_lfm2_game:{fault}"))
    line = execute(cell, 31, 2.0, False, True, time.perf_counter())
    got = line["checks"][check]
    assert line["correct"] is False and got["value"] > got["limit"], got


def test_the_control_in_fp8_is_not_correct(cell):
    """The reference in fp8 in the program's place puts other tokens
    first: the cell's own limit sees it."""
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


# -- the expert layers' floor at this expert size -----------------------------

LM_SIZES = {"hidden_size": 2048, "moe_intermediate_size": 1536,
            "dtype": "bfloat16"}
EXPERT_BYTES = 3 * 2048 * 1536 * 2
ROOFLINE_ARGS = {
    "scope": "moe_experts",
    "floor": "benchmarks.references.lfm2_moe:moe_floor_s",
    "counters": {"experts_touched": "moe.experts_touched",
                 "assignments_held": "moe.assignments_held"}}


def test_the_metric_files_name_this_configurations_floor(cell):
    assert cell.reader_spec("lfm2_moe_roofline_pct") == {
        "reader": "moe_roofline", "args": ROOFLINE_ARGS}
    assert cell.reader_spec("lfm2_moe_experts_pct")["args"] == {
        "scope": "moe_experts"}
    assert cell.reader_spec("lfm2_short_conv_pct")["args"] == {
        "scope": "short_conv"}
    ours = {m["name"] for m in cell.per_layer}
    assert {"lfm2_moe_roofline_pct", "lfm2_moe_experts_pct",
            "lfm2_short_conv_pct", "image_ms", "lm_ms", "prompt_batch_mean",
            "mfu.round", "device_idle_pct", "round_ms",
            "prompt_queue_wait_ms", "image_lock_wait_ms", "image_host_ms",
            "image_batch_mean"} == ours


def test_moe_floor_is_the_larger_of_bytes_and_flops_at_this_expert_size():
    assert EXPERT_BYTES == 18874368
    # decode: 4 experts a row a layer, each read for one row: the memory
    assert plain.moe_floor_s(LM_SIZES, V5E, 1000, 1000) == pytest.approx(
        1000 * EXPERT_BYTES / 819e9)
    # prefill: 64 experts touched by 4 x 256 assignments: still the memory
    # (16 rows an expert); the MXU bounds past 240 rows an expert
    assert plain.moe_floor_s(LM_SIZES, V5E, 64, 1024) == pytest.approx(
        64 * EXPERT_BYTES / 819e9)
    assert plain.moe_floor_s(LM_SIZES, V5E, 64, 64 * 1000) == pytest.approx(
        64 * 1000 * EXPERT_BYTES / 197e12)


@pytest.mark.parametrize("seed", range(3))
def test_the_roofline_cannot_pass_100_for_a_program_that_reads_what_it_touched(
        seed):
    """A program that takes, for every expert layer's call, at least the
    time the memory needs for the experts the counters say were touched
    reads at most 100, whatever the mix of decode calls (4 experts) and
    prefill calls (up to all 64)."""
    rng = np.random.RandomState(seed)
    calls = np.where(rng.rand(400) < 0.9, 4, rng.randint(20, 65, size=400))
    slack = 1.0 + rng.rand(400) * 3.0
    seconds = calls * EXPERT_BYTES / 819e9 * slack
    trace = {"busy_s": 1.4, "window_s": 1.5, "instructions": [
        row("moe_walk", "jit(lm_decode)/x/moe_experts/moe_walk", s)
        for s in seconds]}
    ctx = {"trace": trace, "window_s": 1.5, "device_kind": V5E,
           "names": {"lm_sizes": LM_SIZES},
           "window": Counters(**{
               "moe.experts_touched": float(calls.sum()),
               "moe.assignments_held": float(calls.sum())})}
    got = moe_roofline.read(ctx, ROOFLINE_ARGS)
    assert 25.0 <= got <= 100.0
