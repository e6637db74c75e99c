"""Device seconds under one of the program's scopes, as a share of the
traced slice's busy time: the instructions of the table (trace.reduce_trace)
whose op name has ``scope`` among its path components (a
``jax.named_scope``, a Flax module, a program's name), summed, over
``busy_s``, times 100. Instructions inside a loop count, each with its own
time; the loop's own event is a container and is not in the table, so the
time between a loop's instructions is in ``busy_s`` and under no scope. No
trace, or no instruction under that scope (a program that has no such
scope): nothing returned."""


def under(row: dict, scope: str) -> bool:
    # the statistic reads "<op_name>:<op type>"
    return scope in row["scope"].rsplit(":", 1)[0].split("/")


def scope_seconds(trace: dict, scope: str) -> float:
    return sum(row["seconds"] for row in trace.get("instructions", [])
               if under(row, scope))


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = scope_seconds(trace, args["scope"])
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
