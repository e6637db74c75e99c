#!/usr/bin/env python3
"""Why the chip waits: one run of a cell's window, read by the program's
own spans, without the reference check.

    python3 benchmarks/tools/idle_by_span.py --workload lfm2_rollover \
        --seed 2147496001 --trace 1

The cell's set-up, warm-up and window as ``benchmarks/run.py`` runs them
(``--trace 1``: the profiler over the mix's ``trace_slice_s`` from the
window's start). Then every per-layer metric of the cell, and the ones
that read the trace by the program's spans (``TRACE_METRICS``, through
their files under ``layer_metrics/``), are read while the trace is still
on disk; with the trace, each span's share of the idle device
(``harness/host_trace.py``) and every program on the ``XLA Modules``
line. No reference, so no ``correct``: a measurement of where time goes,
not a benchmark run. ``rounds_per_s`` and ``setup_s`` are the result
line's. One JSON object on the last line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

#: metrics that read the traced slice by the program's span and program
#: names (harness/host_trace.py); the benchmark's runner reads none of
#: them, since it removes the trace before its readers run
TRACE_METRICS = ("idle_lm_host_pct", "idle_image_host_pct",
                 "idle_between_pct", "lm_device_ms", "image_device_ms")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=1)
    parser.add_argument("--platform-cpu", action="store_true",
                        help="rehearse the control flow at the tiny size")
    args = parser.parse_args()
    if args.platform_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from benchmarks.harness import compare as cmp
    from benchmarks.harness import host_trace
    from benchmarks.harness.manifest import Cell, load_manifest
    from benchmarks.harness.runner import (
        TRACE_DIR,
        Run,
        device_block,
        read_layer_metrics,
    )
    from benchmarks.harness.trace import reduce_xplane

    jax.config.update("jax_compilation_cache_max_size", -1)
    cell = Cell(load_manifest(), args.workload)
    run = Run(cell, args.seed, args.seconds, bool(args.trace),
              args.platform_cpu)
    run.build()
    measured = asyncio.run(run.measure())
    res = run.results(measured)
    device = device_block()
    ctx = dict(res, cell=cell, sizes=run.sizes, names=run.names,
               device_kind=device["kind"], trace_dir=TRACE_DIR,
               trees=cmp.reference_trees(run.weights.trees, run.sizes,
                                         run.names))
    sources = {"program_counter", "program_span"}
    out = {"workload": cell.name, "seed": args.seed,
           "trace": bool(args.trace),
           "rounds_per_s": res["rounds"] / res["window_s"],
           "setup_s": measured["t_open"] - T_START,
           "rounds": res["rounds"], "failed": res["failed"],
           "window_s": res["window_s"]}
    if args.trace:
        ctx["trace"] = reduce_xplane(TRACE_DIR)
        # a CPU rehearsal times nothing of the device
        sources = sources if args.platform_cpu else None
        if ctx["trace"]:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            out["idle_gaps"] = ctx["trace"]["idle_gaps"]
        path = host_trace.newest_xplane(TRACE_DIR)
        if path:
            trace = host_trace.load(path)
            out["idle_pct_by_span"] = sorted(
                ([k, 100.0 * v] for k, v in trace.idle.items()),
                key=lambda kv: -kv[1])
            # every program of the slice: executions begun in it, and
            # the mean device ms of those that ran whole inside it
            names = sorted({n for _s, _d, n in trace.modules})
            out["programs"] = {
                n: [sum(1 for m in trace.modules if m[2] == n),
                    trace.module_ms(n)] for n in names}
    metrics = {n: m["value"] for n, m in
               read_layer_metrics(cell, ctx, sources).items()}
    if args.trace:
        for name in TRACE_METRICS:
            spec = cell.reader_spec(name)
            reader = importlib.import_module(
                f"benchmarks.readers.{spec['reader']}")
            value = reader.read(ctx, spec.get("args", {}))
            if value is not None:
                metrics[name] = value
    out["metrics"] = metrics
    out["device"] = device
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # threads of the program's executor may still hold an abandoned
    # dispatch; the result is out, nothing is left to wait for
    os._exit(code)
