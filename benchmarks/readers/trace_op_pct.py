"""One named device operation's share of the traced slice's busy time: its
device seconds among the reduced trace's ranked operations over ``busy_s``,
times 100. No trace, or no operation of that name among the ranked ones
(a program that names its kernel otherwise): nothing returned."""


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = dict(map(tuple, trace.get("device_ops", []))).get(args["op"])
    if seconds is None:
        return None
    return 100.0 * seconds / trace["busy_s"]
