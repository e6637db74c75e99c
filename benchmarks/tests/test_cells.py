"""Each cell end to end at test size with the rehearsal flag (the whole of
run.py but the look for a chip), the control, and the planted faults."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.harness import compare as cmp
from benchmarks.harness.manifest import ROOT, Cell, load_manifest
from benchmarks.harness.runner import execute

M = load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 5), "--seconds", "4",
         "--trace", "1", "--platform-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, "only the result goes to standard output"
    line = json.loads(lines[0])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["correct"] is True, line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert "correct: True" in proc.stderr.strip().splitlines()[-1]
    c = Cell(M, cell)
    assert set(line["checks"]) == set(cmp.required_numbers(
        c.config, c.traffic)) | {"compiles_in_window"}


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.fixture(scope="module")
def play_run(guess_cell):
    """One in-process rehearsal run under the mix with guesses, so with
    every model in it, kept with its book and weights for the control."""
    kept = {}
    real = cmp.compare

    def keeping(book, window, trees, sizes, names, plan, seed, **kw):
        kept.update(book=book, window=window, trees=trees, sizes=sizes,
                    names=names, plan=plan, seed=seed)
        return real(book, window, trees, sizes, names, plan, seed, **kw)

    cmp.compare = keeping
    try:
        line = execute(guess_cell, 11, 2.0, False, True, time.perf_counter())
    finally:
        cmp.compare = real
    return line, kept


def test_the_control_is_not_correct(play_run, guess_cell):
    """The reference in fp8 (int4 for the word table), put in the
    program's place on the same inputs, comes out as not correct, and
    reads at least three times what the program reads in the numbers it
    is held by. The LM's number is not among them: fp8 may put every
    served token first (PERF.md section 2); a wrong token is what fails
    it, below."""
    line, kept = play_run
    assert line["correct"] is True and line["counts"]["guess_calls"] > 10
    sizes, names = kept["sizes"], kept["names"]
    control = cmp.Reference(
        cmp.reference_trees(kept["trees"], sizes, names), sizes, names,
        "fp8")
    values = cmp.compare(kept["book"], kept["window"], kept["trees"], sizes,
                         names, kept["plan"], kept["seed"], served=control)
    correct, checks = cmp.verdict(
        values, guess_cell.config["limits"],
        cmp.required_numbers(guess_cell.config, guess_cell.traffic))
    assert correct is False, checks
    for name in ("image_mean_abs_diff", "score_abs_diff",
                 "table_score_abs_diff"):
        program = line["checks"][name]["value"]
        assert values[name] > 3 * program and values[name] > 1e-3, (
            name, program, values[name])


def test_a_number_that_was_not_compared_is_not_correct():
    limits = {"image_mean_abs_diff": 2.5, "lm_logit_gap": 0.1}
    ok, checks = cmp.verdict({"image_mean_abs_diff": 0.7}, limits,
                             ["image_mean_abs_diff", "lm_logit_gap"])
    assert ok is False
    assert checks["lm_logit_gap"] == {"value": None, "limit": 0.1}
    assert cmp.verdict({"image_mean_abs_diff": 0.7, "lm_logit_gap": 0.01},
                       limits, list(limits))[0] is True


def _broken(monkeypatch, what):
    from cassmantle_tpu.ops.scorer import EmbeddingScorer
    from cassmantle_tpu.serving.pipeline import (
        PromptGenerator,
        Text2ImagePipeline,
    )

    if what == "image":
        real = Text2ImagePipeline.generate

        def generate(self, prompts, seed=0, deadline_s=None):
            out = real(self, prompts, seed=seed, deadline_s=deadline_s)
            return np.where(out > 128, out - 90, out + 90).astype(np.uint8)

        monkeypatch.setattr(Text2ImagePipeline, "generate", generate)
    elif what == "token":
        real = PromptGenerator.decode_ids_batch

        def decode(self, seed_texts, max_new_tokens=None, seed=None):
            toks, lens = real(self, seed_texts, max_new_tokens, seed)
            return (toks + 1) % self.mcfg.vocab_size, lens

        monkeypatch.setattr(PromptGenerator, "decode_ids_batch", decode)
    elif what == "unrecorded":
        # a program that decodes by another way leaves the recorder empty
        from benchmarks.harness import stack

        monkeypatch.setattr(stack, "record_decodes", lambda gen, book: None)
    elif what == "table":
        from cassmantle_tpu.ops.embed_table import EmbedTable

        real = EmbedTable.score_pairs

        def score_pairs(self, pairs):
            scores, served = real(self, pairs)
            return scores * 0.5, served

        monkeypatch.setattr(EmbedTable, "score_pairs", score_pairs)
    else:
        real = EmbeddingScorer.similarity

        def similarity(self, pairs):
            return real(self, pairs) - 0.5

        monkeypatch.setattr(EmbeddingScorer, "similarity", similarity)


@pytest.mark.parametrize("what,check", [
    ("image", "image_mean_abs_diff"), ("token", "lm_logit_gap"),
    ("score", "score_abs_diff"), ("table", "table_score_abs_diff"),
    ("unrecorded", "lm_logit_gap")])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, guess_cell, what, check):
    _broken(monkeypatch, what)
    line = execute(guess_cell, 13, 2.0, False, True, time.perf_counter())
    assert line["correct"] is False
    value, limit = line["checks"][check]["value"], line["checks"][check]["limit"]
    assert value is None if what == "unrecorded" else value > limit
