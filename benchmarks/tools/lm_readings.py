#!/usr/bin/env python3
"""`lm_logit_gap` read on many seeds in one process, at the cell's own LM
size and without the image path (a full run is 2 minutes a seed), at every
batch shape the cell warms.

For each seed: the prompt LM's weights from the seed, then, for each row
count of ``--rows``, every title of the mix's seed file and the stand-in
text decoded through the program's own ``PromptGenerator.decode_ids_batch``
(the call the prompt queue's handler makes, the same compiled programs) in
groups of that many prompts of ONE prompt bucket, so that the program's
grouping by bucket cannot split a group back into smaller dispatches. A
bucket's last short group is filled up with titles from the bucket's start
and the fill dropped. Per prompt and row count, three readings against the
float32 reference:

  program      the served tokens
  control_fp8  the tokens the reference in fp8 puts first at each position
  wrong_low    the served tokens altered as the kept test alters them,
               (t + 1) % vocabulary: the smallest gap over positions; one
               altered token reads at least that

and per row count ``cell_control``: the smallest number the control can
read as a cell's, the worst of any ``check.decodes`` of the prompts (with
18 prompts and 16 decodes, the third largest).

One JSON line per seed.

    python3 benchmarks/tools/lm_readings.py --workload sd15_rollover \
        --seeds 2147483801,2147483802,2147483803 --rows 1,2,4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STAND_IN = "An empty page waited."


def same_bucket_groups(buckets: list, rows: int) -> list:
    """Groups of ``rows`` prompt indices that share a prompt bucket, as
    (indices, kept): the first ``kept`` of a group are its own prompts, the
    rest fill a bucket's last short group from the bucket's start."""
    groups = []
    for bucket in sorted(set(buckets)):
        members = [i for i, b in enumerate(buckets) if b == bucket]
        for at in range(0, len(members), rows):
            own = members[at:at + rows]
            fill = [members[j % len(members)]
                    for j in range(rows - len(own))]
            groups.append((own + fill, len(own)))
    return groups


def cell_control(readings: list, decodes: int) -> float:
    """The smallest number the control reads as a cell's: a run compares
    ``decodes`` of the prompts and takes the worst, so the largest
    ``len(readings) - decodes`` readings may all be left out."""
    return sorted(readings)[min(decodes, len(readings)) - 1]


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rows", default="1,2,4",
                        help="row counts a dispatch, each read in turn")
    parser.add_argument("--platform-cpu", action="store_true")
    args = parser.parse_args()
    if args.platform_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
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
    decodes = cell.config["check"]["decodes"]
    texts = tr.lines(cell.traffic["seed_file"]) + [STAND_IN]
    buckets = [cmp.lm_case(sizes, names, text, [], 0)[2] for text in texts]
    row_counts = [int(r) for r in args.rows.split(",")]
    served_by = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t_seed = time.perf_counter()
        book = WeightBook(seed)
        with book.installed():
            gen = PromptGenerator(cfg, None)
        if served_by is None:
            served_by = gen
        served_by.params = gen.params  # traced arguments: nothing recompiles
        served = {}  # rows -> per prompt (tokens, length)
        for rows in row_counts:
            served[rows] = [None] * len(texts)
            for group, kept in same_bucket_groups(buckets, rows):
                t, k = served_by.decode_ids_batch([texts[i] for i in group])
                t, k = np.asarray(t), np.asarray(k)
                for at, i in enumerate(group[:kept]):
                    served[rows][i] = (t[at], int(k[at]))
        t_served = time.perf_counter()
        tree = {"lm": book.trees[names["lm_weights"]]}
        f32 = cmp.Reference(tree, sizes, names, "f32")
        fp8 = cmp.Reference(tree, sizes, names, "fp8")
        read = {}  # (prompt, served tokens) -> readings: rows that served
        #            the same tokens are the same case for the reference

        def readings(i: int, tokens, length: int) -> dict:
            prompt, toks, bucket = cmp.lm_case(sizes, names, texts[i],
                                               tokens, length)
            toks = np.asarray(toks)
            key = (i, toks.tobytes())
            if key not in read:
                logits = f32.lm_logits(prompt, toks, bucket)
                first = np.asarray(
                    fp8.lm_logits(prompt, toks, bucket)).argmax(axis=-1)
                wrong = cmp.logit_gaps(logits, (toks + 1) % vocab)
                read[key] = {
                    "program": float(cmp.logit_gaps(logits, toks).max()),
                    "control_fp8": float(
                        cmp.logit_gaps(logits, first).max()),
                    "wrong_low": float(wrong.min()),
                    "wrong_high": float(wrong.max()),
                    "served": len(toks)}
            return read[key]

        by_rows, first = {}, served[row_counts[0]]
        for rows in row_counts:
            got = [readings(i, *served[rows][i]) for i in range(len(texts))]
            by_rows[str(rows)] = {
                "program": max(r["program"] for r in got),
                "control_fp8": max(r["control_fp8"] for r in got),
                "cell_control": cell_control(
                    [r["control_fp8"] for r in got], decodes),
                "wrong_token": [min(r["wrong_low"] for r in got),
                                max(r["wrong_high"] for r in got)],
                "served_tokens": sum(r["served"] for r in got),
                "prompts_served_as_at_first_rows": sum(
                    1 for a, b in zip(served[rows], first)
                    if a[1] == b[1] and np.array_equal(a[0], b[0])),
                "per_prompt": {k: [round(r[k], 5) for r in got]
                               for k in ("program", "control_fp8",
                                         "wrong_low")}}
        print(json.dumps({
            "seed": seed, "prompts": len(texts), "buckets": buckets,
            "rows": row_counts,
            "distinct_tokens": len({
                int(t) for per in served.values() for toks, _ in per
                for t in toks}),
            "program": max(r["program"] for r in by_rows.values()),
            "cell_control": min(r["cell_control"] for r in by_rows.values()),
            "by_rows": by_rows,
            "decode_seconds": round(t_served - t_seed, 1),
            "reference_seconds": round(time.perf_counter() - t_served, 1),
        }), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
