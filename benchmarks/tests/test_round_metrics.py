"""The per-layer metrics that read the round's spans (PR 26) and the
kernel's share of its roofline (PR 28): each
entry of BENCHMARK.json resolves to its file and reader, and a rehearsal
of the cell reads the one that is a count."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness.manifest import ROOT, Cell, load_manifest

M = load_manifest()
# name -> (unit, better, source, layer, reader, what it reads)
ROUND_METRICS = {
    "round_ms": ("ms", "lower", "program_span", "serving entry",
                 "hist_mean", "round.content_s"),
    "prompt_queue_wait_ms": ("ms", "lower", "program_span",
                             "batching queues", "hist_mean",
                             "prompt.queue_wait_s"),
    "image_lock_wait_ms": ("ms", "lower", "program_span", "pipelines",
                           "hist_mean", "pipeline.image_lock_wait_s"),
    "image_host_ms": ("ms", "lower", "program_span", "pipelines",
                      "hist_mean", "pipeline.image_host_s"),
    "image_batch_mean": ("rows", "higher", "program_counter", "pipelines",
                         "hist_mean", "pipeline.image_batch_size"),
    "flash_kernel_pct": ("%", "lower", "device_trace", "kernels",
                         "trace_op_pct", "flash_attention"),
    "flash_roofline_pct": ("%", "higher", "device_trace", "kernels",
                           "attention_roofline", "flash_attention"),
}
#: the round's spans and counts are the serving path's, whatever the models:
#: every cell reads them (PR 36), the kernel's two only where it runs
SPAN_CELLS = ["sd15_rollover", "qwen3next_rollover", "lfm2_rollover"]


@pytest.mark.parametrize("name", list(ROUND_METRICS))
def test_entry_resolves_to_its_file_and_reader(name):
    unit, better, source, layer, reader, reads = ROUND_METRICS[name]
    (entry,) = [m for m in M["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "rounds_per_s",
                     "workloads": SPAN_CELLS if reader == "hist_mean"
                     else ["sd15_rollover"]}
    cell = Cell(M, "sd15_rollover")
    assert entry in cell.per_layer
    spec = cell.reader_spec(name)
    assert spec["reader"] == reader
    assert reads in spec["args"].values()
    module = importlib.import_module(f"benchmarks.readers.{reader}")
    assert callable(module.read)


def test_the_new_entries_are_appended_after_the_accepted_five():
    names = [m["name"] for m in M["per_layer"]]
    assert names[:5] == ["image_ms", "lm_ms", "prompt_batch_mean",
                         "mfu.round", "device_idle_pct"]
    assert names[5:12] == list(ROUND_METRICS)


def test_a_rehearsal_reads_the_image_batch_count():
    """The program observes ``pipeline.image_batch_size`` on the path
    the benchmark drives: a CPU rehearsal, which reads counts only,
    lists it (and ``prompt_batch_mean``) and no time."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "sd15_rollover", "--seed", "1", "--seconds", "4",
         "--trace", "1", "--platform-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["counts"]["layer_metrics_read"] == [
        "image_batch_mean", "prompt_batch_mean"]
