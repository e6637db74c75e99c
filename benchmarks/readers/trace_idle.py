"""Share of the traced slice in which no operation ran on the device."""


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    return 100.0 * trace["idle_share"]
