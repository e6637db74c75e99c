"""Share of the traced slice in which the device was idle under some of the
program's spans, by the rule of ``harness/host_trace.py``: each idle
nanosecond goes to the shortest program span open at that instant, waits
left out. ``{"spans": [...]}``: 100 x the idle put down to those spans over
the window; ``{"outside": [...]}``: the idle put down to none of them,
idle under no span included. Summed over a partition of the span names,
the readings are ``device_idle_pct``. An untraced run, or a trace with no
device plane: nothing returned."""

from benchmarks.harness import host_trace


def read(ctx: dict, args: dict):
    trace = host_trace.of_run(ctx)
    if trace is None or not any(trace.devices.values()):
        return None
    if "spans" in args:
        picked = set(args["spans"])
        return 100.0 * sum(v for k, v in trace.idle.items() if k in picked)
    left = set(args["outside"])
    return 100.0 * sum(v for k, v in trace.idle.items() if k not in left)
