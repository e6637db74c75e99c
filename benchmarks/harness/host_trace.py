"""The program's own spans and programs in a profiler trace, and the rule
that puts each idle nanosecond of the device down to one span.

``harness/trace.py`` keeps the benchmark's ``bench.*`` host events alone:
they bound the window, and an idle gap is named after the whole dispatch
that covers most of it. This module reads the same ``.xplane.pb``
(``xplane.load_planes``, once a file) for two things more:

- program spans: host events whose name starts with one of the program's
  layer prefixes (``SPAN_PREFIXES``), the annotation that every span of
  the program opens (``utils/profiling.py``);
- programs: the ``XLA Modules`` line's events of the first device that ran
  anything, named up to ``(``.

**The attribution rule** (``idle_by_span``). Over the window and the busy
union that ``trace.reduce_xplane`` takes for ``idle_share`` (the first to
the last ``bench.*`` event; without one, each device's first to last
operation), each idle nanosecond goes to the SHORTEST program span open
at that instant on any host thread, waits left out (a name that ends
``_wait``: the queues' waits and every lock's ``wait_span``); one under no
such span goes to ``BETWEEN``. So the shares sum to ``idle_share``,
averaged over the devices as it is.
"""

from __future__ import annotations

import functools
import glob
import os

from .trace import HOST_SPAN_PREFIX, OP_LINES, union_ns
from .xplane import load_planes

#: the program's span names start with their layer (obs/trace.py,
#: utils/profiling.py); ``bench.*`` is the benchmark's own
SPAN_PREFIXES = ("round.", "prompt.", "pipeline.", "scorer.", "score.",
                 "decode.", "host.")
MODULE_LINE = "XLA Modules"
#: idle under no program span other than a wait
BETWEEN = "between_spans"


def is_wait(name: str) -> bool:
    return name.endswith("_wait")


def newest_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def put_down(g0: int, g1: int, spans, table: dict) -> None:
    """Add the idle stretch [g0, g1) to ``table`` by the rule: each piece
    to the shortest span open over it (ties by name), ``BETWEEN`` where
    none is. ``spans``: (start_ns, duration_ns, name), waits left out."""
    over = [sp for sp in spans if sp[0] < g1 and sp[0] + sp[1] > g0]
    cuts = sorted({g0, g1} | {t for s, d, _ in over
                              for t in (s, s + d) if g0 < t < g1})
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(d, name) for s, d, name in over if s <= a and s + d >= b]
        name = min(open_)[1] if open_ else BETWEEN
        table[name] = table.get(name, 0) + (b - a)


def idle_by_span(devices: dict, spans, window=None) -> dict:
    """{span name or ``BETWEEN``: share of the window the device was idle
    under it}, averaged over the devices that ran anything, as
    ``reduce_xplane`` averages ``idle_share``. ``devices``: device ->
    operation intervals (start_ns, duration_ns); ``spans``: program spans
    (start_ns, duration_ns, name), waits among them left out here."""
    work = [sp for sp in spans if not is_wait(sp[2])]
    shares = []
    for ops in devices.values():
        if not ops:
            continue
        w0, w1 = window or (min(s for s, _ in ops),
                            max(s + d for s, d in ops))
        busy = union_ns([(max(s, w0), min(s + d, w1)) for s, d in ops
                         if s + d > w0 and s < w1])
        table: dict = {}
        cursor = w0
        for s, e in busy + [[w1, w1]]:
            if s > cursor:
                put_down(cursor, s, work, table)
            cursor = max(cursor, e)
        shares.append({k: v / (w1 - w0) for k, v in table.items()})
    if not shares:
        return {}
    names = {k for table in shares for k in table}
    return {k: sum(t.get(k, 0.0) for t in shares) / len(shares)
            for k in names}


def module_ms(modules, name: str, window=None):
    """Mean device milliseconds of one execution of the program ``name``
    over its executions that ran whole inside ``window`` (all without
    one); None when none did."""
    w0, w1 = window or (float("-inf"), float("inf"))
    runs = [d for s, d, n in modules if n == name and s >= w0 and s + d <= w1]
    return sum(runs) / len(runs) / 1e6 if runs else None


class HostTrace:
    """One ``.xplane.pb`` as the program's spans, the benchmark's window,
    each device's operation intervals and the first busy device's
    programs."""

    def __init__(self, planes: list) -> None:
        self.devices: dict = {}
        modules: dict = {}
        self.spans, bench = [], []
        for plane in planes:
            if plane["name"].startswith("/device:TPU:"):
                ops = self.devices.setdefault(plane["name"], [])
                for line in plane["lines"]:
                    if line["name"] in OP_LINES:
                        ops += [(s, d) for s, d, _n, _st in line["events"]]
                    elif line["name"] == MODULE_LINE:
                        modules.setdefault(plane["name"], []).extend(
                            (s, d, n.split("(", 1)[0])
                            for s, d, n, _st in line["events"])
            elif plane["name"].startswith("/host:"):
                for line in plane["lines"]:
                    for s, d, n, _st in line["events"]:
                        if n.startswith(SPAN_PREFIXES):
                            self.spans.append((s, d, n))
                        elif n.startswith(HOST_SPAN_PREFIX):
                            bench.append((s, d))
        self.window = ((min(s for s, _ in bench),
                        max(s + d for s, d in bench)) if bench else None)
        busy = sorted(k for k, ops in self.devices.items() if ops)
        self.modules = modules.get(busy[0], []) if busy else []

    @functools.cached_property
    def idle(self) -> dict:
        return idle_by_span(self.devices, self.spans, self.window)

    def module_ms(self, name: str):
        return module_ms(self.modules, name, self.window)


@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime_ns: int, _size: int) -> HostTrace:
    return HostTrace(load_planes(path))


def load(path: str) -> HostTrace:
    """The file's ``HostTrace``, parsed once while the file stays as it
    is."""
    st = os.stat(path)
    return _load(path, st.st_mtime_ns, st.st_size)


def of_run(ctx: dict):
    """The traced slice of a run as a ``HostTrace``: the newest file under
    ``ctx["trace_dir"]`` (the runner's trace directory without one); None
    for an untraced run or when no file is there."""
    if not ctx.get("trace"):
        return None
    trace_dir = ctx.get("trace_dir")
    if trace_dir is None:
        from .runner import TRACE_DIR as trace_dir
    path = newest_xplane(trace_dir)
    return load(path) if path else None
