"""An attention kernel's share of its roofline over the traced slice: for
every call of the named device instruction that ran whole in the slice,
the least time the chip could take for its site (harness/rooflines.py),
summed, over the calls' device seconds, times 100. No trace, or no call
of that name in it: nothing returned. A call whose site cannot be told is
an error."""

from benchmarks.harness import rooflines


def read(ctx: dict, args: dict):
    calls = [row for row in (ctx.get("trace") or {}).get("instructions", [])
             if row["name"] == args["op"]]
    seconds = sum(row["seconds"] for row in calls)
    if not seconds:
        return None
    sites = rooflines.attention_sites(ctx["trees"], ctx["sizes"],
                                      ctx["names"])
    floor = 0.0
    for row in calls:
        site, element_bytes = rooflines.attention_call(row["hlo"], sites)
        floor += row["calls"] * rooflines.attention_floor_s(
            site, element_bytes, ctx["device_kind"])
    return 100.0 * floor / seconds
