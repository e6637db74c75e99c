"""A configuration with another prompt LM, or another image trajectory,
arrives as files alone: the CPU rehearsal of a whole run (run.py without
its look for a chip) on test configurations built from files under
``benchmarks/tests/`` only (named_fixture.py)."""

import json
import os
import time

import pytest

from benchmarks.harness import compare as cmp
from benchmarks.harness import flops
from benchmarks.harness.manifest import Cell, load_manifest
from benchmarks.harness.runner import execute
from benchmarks.tests.test_cells import _broken

HERE = os.path.dirname(__file__)


def committed_cell() -> Cell:
    manifest = load_manifest()
    return Cell(manifest, manifest["workloads"][0]["name"])


@pytest.fixture()
def mistral_cell():
    """The committed cell's traffic under the test configuration whose
    prompt LM is the program's second family."""
    cell = committed_cell()
    with open(os.path.join(HERE, "mistral_test.json")) as f:
        cell.config = json.load(f)
    return cell


def euler_cell(trajectories: dict) -> Cell:
    """The committed cell with its sampler's kind overridden, under the
    trajectories a test's file would name."""
    cell = committed_cell()
    cell.config = dict(cell.config,
                       overrides={"sampler": {"kind": "euler"}},
                       image_trajectory=trajectories)
    return cell


def test_another_prompt_lm_is_compared_through_its_own_reference(
        mistral_cell):
    line = execute(mistral_cell, 21, 2.0, False, True, time.perf_counter())
    assert line["correct"] is True, line["checks"]
    gap = line["checks"]["lm_logit_gap"]
    assert gap["value"] is not None and gap["value"] <= gap["limit"]
    assert line["checks"]["image_mean_abs_diff"]["value"] is not None
    assert line["counts"]["rounds"] >= 4


def test_the_file_is_checked_and_an_untraced_run_notes_its_spans(
        mistral_cell):
    """The fixture's file states the tiny sizes themselves, so the path a
    chip run takes (the file's sizes, checked against the program's; no
    rehearsal flag) runs here: its line carries the end-to-end metrics
    and, in its notes, the program's spans and counts over the window.
    A size the program does not run stops it at set-up."""
    line = execute(mistral_cell, 25, 2.0, False, False, time.perf_counter())
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"rounds_per_s", "setup_s"}
    spans = line["notes"]["spans"]
    assert {"round_ms", "image_ms", "lm_ms", "image_lock_wait_ms",
            "prompt_batch_mean"} <= set(spans)
    assert "mfu.round" not in spans and "device_idle_pct" not in spans
    assert all(v > 0 for v in spans.values())
    mistral_cell.config["sizes"]["mistral"]["num_kv_heads"] = 4
    with pytest.raises(SystemExit, match="mistral.num_kv_heads: file 4"):
        execute(mistral_cell, 25, 2.0, False, False, time.perf_counter())


def test_its_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, mistral_cell):
    _broken(monkeypatch, "token")
    line = execute(mistral_cell, 22, 2.0, False, True, time.perf_counter())
    gap = line["checks"]["lm_logit_gap"]
    assert line["correct"] is False and gap["value"] > gap["limit"]


def test_lm_flops_through_the_named_function_is_the_hand_count(
        mistral_cell):
    """Matmuls of the tiny model over n tokens, 2*M*N*K each: per layer
    q and out (64x64), k and v (64x32), the three SwiGLU matrices
    (64x128) and QK^T and PV over 4 heads of 16 (all n keys a query, as
    every reference counts attention); the head (64x256)."""
    import jax

    from benchmarks.harness.stack import framework_config
    from cassmantle_tpu.models.mistral import MistralLM

    config = mistral_cell.config
    cfg = framework_config(config, True)
    names = cmp.named(config, config["sizes"])
    tree = jax.eval_shape(MistralLM(cfg.models.mistral).init,
                          jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), "int32"))
    n = 30 + 8
    per_token = 2 * (2 * 64 * 64 * 2 + 2 * 64 * 32 * 2 + 2 * 64 * 128 * 3) \
        + 2 * 64 * 256
    by_hand = n * per_token + 2 * (4 * 4 * n * n * 16)
    assert flops.lm_flops({"lm": tree}, names, 30, 8) == by_hand


def test_another_trajectory_is_compared_through_its_own_loop():
    cell = euler_cell({
        "ddim": "benchmarks.harness.reference:ddim_trajectory",
        "euler": "benchmarks.tests.euler_reference:euler_trajectory"})
    line = execute(cell, 23, 2.0, False, True, time.perf_counter())
    image = line["checks"]["image_mean_abs_diff"]
    assert image["value"] is not None and image["value"] <= image["limit"]
    assert line["correct"] is True, line["checks"]


def test_a_sampler_kind_with_no_trajectory_named_is_a_set_up_error():
    cell = euler_cell({
        "ddim": "benchmarks.harness.reference:ddim_trajectory"})
    with pytest.raises(SystemExit, match="names no image trajectory"):
        execute(cell, 24, 2.0, False, True, time.perf_counter())
