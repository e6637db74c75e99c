#!/usr/bin/env python3
"""Where a round's device time goes, by the names the program gives it.

Builds a cell's stack, runs its traffic warm, captures one slice of the
profiler's trace (the mix's ``trace_slice_s``, or ``--slice-s``) and
prints three tables:

(a) device time of one dispatch of each program that ran whole inside the
    capture, by scope: each device event's op name (the ``op_name`` the
    program's ``jax.named_scope``s, Flax modules and Pallas ``name=``s
    wrote into the compiled program), by program and stage, then by UNet
    level and block kind, then the largest XLA operations split the same
    way;
(b) the programs, from the ``XLA Modules`` line: whole dispatches and
    device time a dispatch (the LM's own device time, beside its time on
    the host clock);
(c) every idle gap over 50 us with the program span that covers it (the
    program's spans are in the trace as annotations of the same names).

    python3 benchmarks/tools/round_anatomy.py --workload sd15_rollover \
        --seed 2147483801 --slice-s 2.2

The reduction (``anatomy``) works on plain intervals, like
``harness/trace.py::reduce_trace``, so a test feeds it a trace it wrote
itself. The benchmark's own reduction keeps each instruction's scope in
its table for the readers and reduces by none yet (PERF.md section 7).
One JSON object on the last line.
"""

from __future__ import annotations

import argparse
import asyncio
import bisect
import glob
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

from benchmarks.harness.trace import (  # noqa: E402
    CONTAINERS,
    OP_LINES,
    short_name,
    union_ns,
)
from benchmarks.harness.xplane import SCOPE_STAT, load_planes  # noqa: E402

MODULE_LINE = "XLA Modules"
#: host spans that explain a gap: the program's own (obs/trace.py names a
#: span ``<layer>.<what>``) and the benchmark's ``bench.*``
SPAN_PREFIXES = ("round.", "prompt.", "pipeline.", "scorer.", "score.",
                 "decode.", "bench.")
MIN_GAP_NS = 50_000
#: path components the tracing machinery adds, not the program
PLUMBING = re.compile(
    r"^(while|body|cond|branch_\d+_fun|closed_call|checkpoint|remat\d*"
    r"|pjit|jit|jit\(.*\)|jvp\(.*\)|transpose\(.*\)|vmap\(.*\))$")
LEVEL = re.compile(r"^(down|up)_(\d+)_(res|attn|downsample|upsample)"
                   r"|^(mid)_(res|attn)")


# -- the trace file ---------------------------------------------------------
def load_trace(trace_dir: str):
    """(device ops ``(start, duration, name, scope)``, programs ``(start,
    duration, name)``, host spans ``(start, duration, name)``) of the
    newest trace under ``trace_dir``; the first device that ran anything."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, programs, host = [], [], []
    for plane in load_planes(paths[-1]):
        if plane["name"].startswith("/device:TPU:") and not ops:
            for line in plane["lines"]:
                if line["name"] in OP_LINES:
                    ops = [(s, d, short_name(n), st.get(SCOPE_STAT, ""))
                           for s, d, n, st in line["events"]]
                elif line["name"] == MODULE_LINE:
                    programs = [(s, d, n.split("(", 1)[0])
                                for s, d, n, _st in line["events"]]
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                host += [(s, d, n) for s, d, n, _st in line["events"]
                         if n.startswith(SPAN_PREFIXES)]
    return ops, programs, host


# -- the reduction ----------------------------------------------------------
def scope_path(scope: str) -> list:
    """"jit(t2i_sample)/denoise_scan/while/body/denoise_step/UNet/mid_attn/
    block_0/ff/proj/dot_general:" -> [t2i_sample, denoise_scan,
    denoise_step, UNet, mid_attn, block_0, ff, proj]: the program, then
    the program's scopes; the primitive at the end and what tracing adds
    in between are dropped."""
    # the statistic reads "<op_name>:<op type>", the type often empty
    parts = [p for p in scope.rsplit(":", 1)[0].split("/") if p]
    if not parts:
        return []
    root = re.match(r"^jit\((.*)\)$", parts[0])
    head = [root.group(1)] if root else []
    rest = parts[1:] if root else parts
    return head + [p for p in rest[:-1] if not PLUMBING.match(p)]


def block_of(path: list) -> str:
    """The UNet level and block kind of a scope path ("down_1 attn.ff"),
    the stage for what lies outside the UNet, "(no scope)" for none."""
    if not path:
        return "(no scope)"
    if "UNet" not in path:
        return "/".join(path[:2])
    below = path[path.index("UNet") + 1:]
    m = LEVEL.match(below[0]) if below else None
    if not m:
        return "UNet " + (below[0] if below else "(top)")
    level = f"{m.group(1)}_{m.group(2)}" if m.group(1) else "mid"
    kind = m.group(3) or m.group(5)
    if kind == "attn":
        inner = [p for p in below[1:] if not p.startswith("block_")]
        part = inner[0] if inner else "(top)"
        kind = "attn." + (part if part in (
            "self_attn", "cross_attn", "ff") else "norm_proj")
    return f"{level} {kind}"


def covering_span(host_spans, g0: int, g1: int) -> str:
    """The span a gap is named after: the shortest of those that cover at
    least half of it (a round's span covers everything its children do),
    else the one that covers most, else "no_program_span"."""
    covers = [(min(g1, s + d) - max(g0, s), d, name)
              for s, d, name in host_spans]
    covers = [c for c in covers if c[0] > 0]
    if not covers:
        return "no_program_span"
    half = [c for c in covers if 2 * c[0] >= g1 - g0]
    if half:
        return min(half, key=lambda c: c[1])[2]
    return max(covers, key=lambda c: c[0])[2]


def anatomy(ops, programs, host_spans, window=None, depth: int = 3,
            top: int = 12, min_gap_ns: int = MIN_GAP_NS) -> dict:
    """ops: (start_ns, duration_ns, name, scope); programs and
    host_spans: (start_ns, duration_ns, name). ``window`` (start_ns,
    end_ns) bounds the capture and defaults to the span of the ops.

    Device time is counted over the programs that ran WHOLE inside the
    window and reported a dispatch of its program (one image, one decode),
    so the tables do not depend on where the capture cut a program; busy
    time and idle gaps are taken over the whole window. Seconds
    throughout."""
    if not ops:
        return {}
    if window is None:
        window = (min(o[0] for o in ops), max(o[0] + o[1] for o in ops))
    w0, w1 = window
    whole = sorted((s, s + d, name) for s, d, name in programs
                   if s >= w0 and s + d <= w1)
    starts = [s for s, _e, _n in whole]
    dispatches: dict = {}
    for s, e, name in whole:
        row = dispatches.setdefault(name, [0, 0])
        row[0] += 1
        row[1] += e - s
    by_scope: dict = {}
    by_block: dict = {}
    by_op: dict = {}
    total = scoped = 0.0
    for s, d, name, scope in ops:
        at = bisect.bisect_right(starts, s) - 1
        if name in CONTAINERS or at < 0 or s + d > whole[at][1]:
            continue
        ns = d / dispatches[whole[at][2]][0]     # a dispatch of its program
        path = scope_path(scope)
        total += ns
        scoped += ns if len(path) > 1 else 0
        for k in range(1, min(depth, len(path)) + 1):
            key = "/".join(path[:k])
            by_scope[key] = by_scope.get(key, 0) + ns
        block = block_of(path)
        by_block[block] = by_block.get(block, 0) + ns
        split = by_op.setdefault(name, {})
        split[block] = split.get(block, 0) + ns

    busy = union_ns([(max(s, w0), min(s + d, w1)) for s, d, _n, _sc in ops
                     if s + d > w0 and s < w1])
    gaps, cursor = [], w0
    for s, e in busy + [[w1, w1]]:
        if s - cursor >= min_gap_ns:
            gaps.append([(cursor - w0) / 1e9, (s - cursor) / 1e9,
                         covering_span(host_spans, cursor, s)])
        cursor = max(cursor, e)

    def ranked(table, n=None):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in rows]

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "device_s": total / 1e9,
        "scoped_share": scoped / total if total else 0.0,
        "by_scope": sorted([k, v / 1e9] for k, v in by_scope.items()),
        "by_block": ranked(by_block),
        "by_op": [[name, sum(split.values()) / 1e9, ranked(split, 6)]
                  for name, split in sorted(
                      by_op.items(),
                      key=lambda kv: -sum(kv[1].values()))[:top]],
        "by_program": [[name, n, ns / n / 1e9] for name, (n, ns) in sorted(
            dispatches.items(), key=lambda kv: -kv[1][1])],
        "gaps": gaps,
    }


def render(a: dict) -> str:
    if not a:
        return "no device operation in the trace"
    if not a["device_s"]:
        return (f"no program ran whole inside the {a['window_s']:.3f} s "
                f"capture: take a longer slice")
    dev = a["device_s"]

    def row(s, label):
        return f"  {1e3 * s:9.3f} ms {100 * s / dev:5.1f}%  {label}"

    out = [f"capture {a['window_s']:.4f} s, busy {a['busy_s']:.4f} s. "
           f"Device time of ONE dispatch of each program that ran whole "
           f"in it: {1e3 * dev:.3f} ms, {100 * a['scoped_share']:.1f}% of "
           f"it under a scope below its program's root", "",
           "(a) device time a dispatch, by scope"]
    for key, s in a["by_scope"]:
        if s / dev >= 0.002:
            out.append(row(s, "  " * key.count("/")
                           + key.rsplit("/", 1)[-1]))
    out += ["", "    by UNet level and block kind"]
    out += [row(s, k) for k, s in a["by_block"] if s / dev >= 0.002]
    for title, part in (("level", 0), ("block kind", 1)):
        rolled: dict = {}
        for k, s in a["by_block"]:
            if LEVEL.match(k.replace(" ", "_", 1)):
                key = k.split(" ", 1)[part]
                rolled[key] = rolled.get(key, 0) + s
        out += ["", f"    the UNet by {title}"]
        out += [row(s, k) for k, s in sorted(rolled.items(),
                                             key=lambda kv: -kv[1])]
    out += ["", "    the largest XLA operations, split the same way"]
    for name, s, split in a["by_op"]:
        parts = ", ".join(f"{k} {1e3 * v:.2f}" for k, v in split)
        out.append(row(s, f"{name}: {parts}"))
    out += ["", "(b) programs (XLA Modules): whole dispatches in the "
            "capture, device time a dispatch"]
    out += [f"  {n:3d}  {1e3 * each:9.3f} ms  {name}"
            for name, n, each in a["by_program"]]
    out += ["", f"(c) idle gaps of {MIN_GAP_NS / 1e3:.0f} us and more: "
            f"{len(a['gaps'])}, {1e3 * sum(g[1] for g in a['gaps']):.3f} ms"]
    out += [f"  at {at:9.6f} s  {1e6 * length:9.1f} us  {name}"
            for at, length, name in a["gaps"]]
    return "\n".join(out)


# -- one run ----------------------------------------------------------------
def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--slice-s", type=float, default=None,
                        help="seconds to capture (default: the mix's "
                        "trace_slice_s); two of the longest program's "
                        "times hold one whole dispatch of it")
    parser.add_argument("--platform-cpu", action="store_true",
                        help="rehearse the control flow at the tiny size")
    args = parser.parse_args()
    if args.platform_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmarks.harness.manifest import Cell, load_manifest
    from benchmarks.harness.runner import TRACE_DIR, Run

    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)
    cell = Cell(load_manifest(), args.workload)
    if args.slice_s is not None:
        cell.traffic["trace_slice_s"] = args.slice_s
    # the cell's own warm-up and closed loop, with the profiler on for
    # the slice from the window's start; the window closes at the opening
    # room's first round that completes after the slice
    run = Run(cell, args.seed, cell.traffic["trace_slice_s"], True,
              args.platform_cpu)
    run.build()
    asyncio.run(run.measure())
    ops, programs, host = load_trace(TRACE_DIR)
    if args.platform_cpu and not ops:
        print("rehearsal: a CPU trace has no device plane; host spans "
              f"found: {sorted({n for _s, _d, n in host})}")
        return 0
    result = anatomy(ops, programs, host)
    print(render(result))
    result["device"] = {"platform": jax.local_devices()[0].platform,
                        "kind": jax.local_devices()[0].device_kind}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
