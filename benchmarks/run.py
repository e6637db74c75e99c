#!/usr/bin/env python3
"""python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json. The last line of standard output is
the result, one JSON object; everything else the process prints goes to
standard error. Exits non-zero, with no result, when jax finds no TPU or
fewer chips than the cell asks for. ``--platform-cpu`` is a rehearsal of
the control flow on the CPU at the configuration's tiny test size: its
line is marked ``"rehearsal": true`` and carries counts, no metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def claim_stdout():
    """Keep the real stdout for the result line and point fd 1 (and
    ``sys.stdout``) at stderr for everything else in the process (copied
    from chip_smoke.py)."""
    own = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return own


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--platform-cpu", action="store_true")
    args = parser.parse_args()
    out = claim_stdout()
    sys.path.insert(0, ROOT)
    if args.platform_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the compile cache lives inside the checkout, at a fixed path, whatever
    # the machine's environment says: the program sets no directory of its
    # own once this is set (utils/compile_cache.py)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

    from benchmarks.harness.manifest import Cell, load_manifest

    cell = Cell(load_manifest(), args.workload)
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.local_devices()
    if not args.platform_cpu and (devices[0].platform != "tpu"
                                  or len(devices) < cell.chips):
        print(f"{args.workload} needs {cell.chips} TPU chip(s); jax found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2

    from benchmarks.harness.runner import execute

    line = execute(cell, args.seed, args.seconds, bool(args.trace),
                   args.platform_cpu, T_START)
    for name, check in line["checks"].items():
        print(f"check {name}: {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    out.write(json.dumps(line) + "\n")
    out.flush()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # threads of the program's executor may still hold an abandoned
    # dispatch; the result is out, nothing is left to wait for
    os._exit(code)
