"""From a profiler trace to busy time, idle share, top operations, what
the host was doing in the longest idle gaps, and the table of device
instructions that the per-layer readers draw on.

The reduction works on plain intervals (start_ns, duration_ns, name[, hlo,
scope]) so a test can hand it a trace it wrote itself; ``load_xplane``
turns the profiler's ``.xplane.pb`` into those intervals.
"""

from __future__ import annotations

import glob
import os

from .xplane import SCOPE_STAT, load_planes


def union_ns(intervals) -> list:
    """Merged [start, end) list of possibly overlapping intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def reduce_trace(device_ops, host_spans, window=None, top: int = 10) -> dict:
    """host_spans: (start_ns, duration_ns, name); device_ops the same,
    or with the event's whole HLO text and its scope after the name.

    ``window`` (start_ns, end_ns) defaults to the span of the device ops.
    Busy is the union of device-op intervals clipped to the window; a gap
    is a maximal idle stretch inside it, named after the host span that
    covers most of it ("no_benchmark_span" when none does).

    ``instructions`` is for the readers, not for the result line: seconds
    and calls of every device instruction, keyed by its short name with
    what tells one call site from another, the event's whole HLO text (its
    result and operand shapes) and its scope. It counts the calls that ran
    whole inside the window, each with its whole time, so that seconds
    over calls is the time of a call."""
    if not device_ops:
        return {}
    if window is None:
        window = (min(op[0] for op in device_ops),
                  max(op[0] + op[1] for op in device_ops))
    w0, w1 = window
    clipped = [(max(op[0], w0), min(op[0] + op[1], w1)) for op in device_ops
               if op[0] + op[1] > w0 and op[0] < w1]
    busy = union_ns(clipped)
    busy_ns = sum(e - s for s, e in busy)
    by_op: dict = {}
    by_instruction: dict = {}
    for s, d, name, *site in device_ops:
        lo, hi = max(s, w0), min(s + d, w1)
        if hi <= lo or name in CONTAINERS:
            continue
        by_op[name] = by_op.get(name, 0) + (hi - lo)
        if hi - lo == d:
            hlo, scope = (site + ["", ""])[:2]
            row = by_instruction.setdefault((name, hlo, scope), [0, 0])
            row[0] += d
            row[1] += 1
    gaps, cursor = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    by_gap: dict = {}
    for g0, g1 in gaps:
        best, best_cover = "no_benchmark_span", 0
        for s, d, name in host_spans:
            cover = min(g1, s + d) - max(g0, s)
            if cover > best_cover:
                best, best_cover = name, cover
        by_gap[best] = by_gap.get(best, 0) + (g1 - g0)

    def ranked(table):
        return [[name, ns / 1e9] for name, ns in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / (w1 - w0),
        "device_ops": ranked(by_op),
        "idle_gaps": ranked(by_gap),
        "n_device_ops": len(device_ops),
        "instructions": [
            {"name": name, "hlo": hlo, "scope": scope, "seconds": ns / 1e9,
             "calls": calls}
            for (name, hlo, scope), (ns, calls) in by_instruction.items()],
    }


#: device-plane lines that hold one event per executed operation
OP_LINES = ("XLA Ops",)
#: operations that only contain others: they count as busy time, and are
#: left out of the ranking, where their children stand
CONTAINERS = ("while", "conditional", "call")


def short_name(event_name: str) -> str:
    """"%fusion.6140 = bf16[...] fusion(...)" -> "fusion": the profiler
    names a device event by its whole HLO instruction."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = name.rpartition(".")
    return (base if base and suffix.isdigit() else name)[:64]
HOST_SPAN_PREFIX = "bench."


def load_xplane(trace_dir: str):
    """(per-device op lists, host spans) of the newest trace under
    ``trace_dir``; a device op is (start_ns, duration_ns, short name,
    whole HLO text, scope)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, host = {}, []
    for plane in load_planes(paths[-1]):
        if plane["name"].startswith("/device:TPU:"):
            ops = devices.setdefault(plane["name"], [])
            for line in plane["lines"]:
                if line["name"] in OP_LINES:
                    ops += [(s, d, short_name(n), n, st.get(SCOPE_STAT, ""))
                            for s, d, n, st in line["events"]]
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                host += [(s, d, n) for s, d, n, _st in line["events"]
                         if n.startswith(HOST_SPAN_PREFIX)]
    return devices, host


def reduce_xplane(trace_dir: str) -> dict:
    """Reduction averaged over the devices that ran anything. The window
    is the span from the first to the last benchmark host span, so the
    profiler's own start-up and shut-down are outside it."""
    devices, host = load_xplane(trace_dir)
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return {}
    window = None
    if host:
        window = (min(s for s, _, _ in host),
                  max(s + d for s, d, _ in host))
    parts = [reduce_trace(ops, host, window) for ops in devices.values()]
    parts = [p for p in parts if p]
    first = parts[0]
    n = len(parts)
    return dict(first,
                busy_s=sum(p["busy_s"] for p in parts) / n,
                idle_share=sum(p["idle_share"] for p in parts) / n)
