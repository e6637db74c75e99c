#!/usr/bin/env python3
"""The control, on the chip at the cell's own size: for each seed one short
window of the cell, then the numbers `correct` compares read twice on the
same inputs: from what the program served, and from the reference in fp8
put in the program's place, each through the verdict a run gets (the
control's has to be false). One process, one JSON line per seed.

    python3 benchmarks/tools/control_run.py --workload sd15_rollover \
        --seeds 2147483701,2147483702,2147483703 --seconds 4
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
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--platform-cpu", action="store_true")
    args = parser.parse_args()
    if args.platform_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmarks.harness import compare as cmp
    from benchmarks.harness.manifest import Cell, load_manifest
    from benchmarks.harness.runner import run_window

    cell = Cell(load_manifest(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_window(cell, seed, args.seconds, False, args.platform_cpu,
                         time.perf_counter())
        common = (res["book"], res["span"], res["trees"], res["sizes"],
                  res["names"], cell.config["check"], seed)
        t0 = time.perf_counter()
        program = cmp.compare(*common)
        t1 = time.perf_counter()
        control = cmp.compare(*common, served=cmp.Reference(
            cmp.reference_trees(res["trees"], res["sizes"], res["names"]),
            res["sizes"], res["names"], "fp8"))
        limits = cell.config["limits"]
        required = cmp.required_numbers(cell.config, cell.traffic)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "program": program,
            "program_correct": cmp.verdict(program, limits, required)[0],
            "control_fp8": control,
            "control_correct": cmp.verdict(control, limits, required)[0],
            "reference_seconds": round(t1 - t0, 1),
            "control_seconds": round(time.perf_counter() - t1, 1),
            "rounds": res["rounds"], "failed": res["failed"]}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
