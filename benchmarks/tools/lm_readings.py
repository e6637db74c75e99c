#!/usr/bin/env python3
"""`lm_logit_gap` read on many seeds in one process, at the cell's own LM
size and without the image path (a full run is 2 minutes a seed).

For each seed: the prompt LM's weights from the seed, then every title of
the mix's seed file and the stand-in text decoded through the program's own
``PromptGenerator.decode_ids_batch`` (the call the prompt queue's handler
makes, the same compiled programs, batches of 1, 2 and 4). Per prompt, three
readings against the float32 reference:

  program      the served tokens
  control_fp8  the tokens the reference in fp8 puts first at each position
  wrong_token  the served tokens altered as the kept test alters them,
               (t + 1) % vocabulary: [smallest, largest] gap over positions;
               one altered token reads at least the smallest

One JSON line per seed.

    python3 benchmarks/tools/lm_readings.py --workload sd15_rollover \
        --seeds 2147483801,2147483802,2147483803
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

STAND_IN = "An empty page waited."


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--platform-cpu", action="store_true")
    args = parser.parse_args()
    if args.platform_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from benchmarks.harness import compare as cmp
    from benchmarks.harness import traffic as tr
    from benchmarks.harness.manifest import Cell, load_manifest
    from benchmarks.harness.stack import framework_config, program_sizes
    from benchmarks.harness.weights import WeightBook
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    cell = Cell(load_manifest(), args.workload)
    cfg = framework_config(cell.config, args.platform_cpu)
    sizes = (program_sizes(cfg, cell.config) if args.platform_cpu
             else cell.config["sizes"])
    names = cmp.named(cell.config, sizes)
    vocab = names["lm_sizes"]["vocab_size"]
    texts = tr.lines(cell.traffic["seed_file"]) + [STAND_IN]
    served_by = None
    for seed in (int(s) for s in args.seeds.split(",")):
        book = WeightBook(seed)
        with book.installed():
            gen = PromptGenerator(cfg, None)
        if served_by is None:
            served_by = gen
        served_by.params = gen.params  # traced arguments: nothing recompiles
        tokens, lengths, i = [], [], 0
        for n in (1, 2, 4) * len(texts):
            if i >= len(texts):
                break
            t, k = served_by.decode_ids_batch(texts[i:i + n])
            tokens += list(np.asarray(t))
            lengths += list(np.asarray(k))
            i += n
        tree = {"lm": book.trees[names["lm_weights"]]}
        f32 = cmp.Reference(tree, sizes, names, "f32")
        fp8 = cmp.Reference(tree, sizes, names, "fp8")
        rows = {"program": [], "control_fp8": [], "wrong_low": [],
                "wrong_high": [], "served": []}
        for text, toks, length in zip(texts, tokens, lengths):
            prompt, served, bucket = cmp.lm_case(sizes, names, text, toks,
                                                 length)
            served = np.asarray(served)
            logits = f32.lm_logits(prompt, served, bucket)
            first = np.asarray(
                fp8.lm_logits(prompt, served, bucket)).argmax(axis=-1)
            wrong = cmp.logit_gaps(logits, (served + 1) % vocab)
            rows["program"].append(float(cmp.logit_gaps(logits, served).max()))
            rows["control_fp8"].append(
                float(cmp.logit_gaps(logits, first).max()))
            rows["wrong_low"].append(float(wrong.min()))
            rows["wrong_high"].append(float(wrong.max()))
            rows["served"].append(len(served))
        print(json.dumps({
            "seed": seed, "prompts": len(texts),
            "served_tokens": int(sum(rows["served"])),
            "distinct_tokens": len({int(t) for r in tokens for t in r}),
            "program": max(rows["program"]),
            "control_fp8": max(rows["control_fp8"]),
            "wrong_token": [min(rows["wrong_low"]), max(rows["wrong_high"])],
            "per_prompt": {k: [round(v, 5) for v in rows[k]]
                           for k in ("program", "control_fp8", "wrong_low")},
        }), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
