"""The expert layers' share of their roofline: the least time the chip
could take for what the program's counters say was routed in the window
(``floor``, a function kept with the configuration's reference: expert
weights touched over the memory's peak, or the routed FLOPs over the
MXU's if larger), over the device seconds under the expert layers'
``scope`` in the traced slice, times 100. The counters cover the window
and the trace a slice of it, so both are brought to a second of their own
span. The floor comes from counters of what was routed, not from what the
program executed: it is the same whatever implements the layer. No trace,
no instruction under the scope or nothing counted: nothing returned."""

from benchmarks.harness.manifest import resolve
from benchmarks.readers.trace_scope_pct import scope_seconds


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    seconds = scope_seconds(trace, args["scope"])
    counted = {key: ctx["window"].counter(name)
               for key, name in args["counters"].items()}
    if not seconds or not any(counted.values()):
        return None
    floor_s = resolve(args["floor"])(
        ctx["names"]["lm_sizes"], ctx["device_kind"], **counted)
    return 100.0 * (floor_s / ctx["window_s"]) / (
        seconds / trace["window_s"])
