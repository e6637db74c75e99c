"""A decode step's share of its roofline: the least time the chip could
take for a mean step of the window (``floor``, a function kept with the
configuration's reference, over the program's counters), over the device
seconds of a step in the traced slice, times 100.

A step's device seconds come from the instruction table (trace.reduce_trace)
and not from scopes the compiler may move. The steps that ran in the slice
are counted by one instruction that runs once a step: of the instructions
whose op name starts with the program's name (``program``), the one whose
result is a float32 (rows, vocabulary) matrix, a step's logits, that ran
most often. Every instruction of the slice that ran about once a step or
more (its calls over the steps, rounded, at least 1: the stack's, the
cache's, the loop's own waits on its prefetches, scoped or not) belongs to
the step; it takes its mean time that many times. An instruction of the
prefill or of another program runs a few times a slice and counts for
nothing. So the reading is a step's, whichever part of the orbit the slice
holds: slices cut at two phases of one orbit read alike
(benchmarks/tests/test_ouro_game.py). The floor comes from counters of
what was decoded, not from what the program executed: it is the same
whatever implements the loop. No trace, no such instruction or nothing
counted: nothing returned."""

import re

from benchmarks.harness.manifest import resolve


def logits_rows(hlo: str, vocab: int):
    """Rows of an instruction whose result is f32[rows, vocab], else None."""
    hit = re.match(r"f32\[(\d+),(\d+)\]", hlo.split(" = ", 1)[-1])
    return int(hit.group(1)) if hit and int(hit.group(2)) == vocab else None


def step_seconds(instructions: list, program: str, vocab: int):
    """(steps whole in the slice, device seconds of one step) or None."""
    marks = [row["calls"] for row in instructions
             if row["scope"].startswith(program + "/")
             and logits_rows(row["hlo"], vocab)]
    if not marks or not max(marks):
        return None
    steps = max(marks)
    seconds = 0.0
    for row in instructions:
        per_step = round(row["calls"] / steps)
        if per_step >= 1:
            seconds += per_step * row["seconds"] / row["calls"]
    return steps, seconds


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    sizes = ctx["names"]["lm_sizes"]
    counted = {key: ctx["window"].counter(name)
               for key, name in args["counters"].items()}
    found = step_seconds(trace.get("instructions", []), args["program"],
                         sizes["vocab_size"])
    if found is None or not all(counted.values()):
        return None
    floor_s = resolve(args["floor"])(sizes, ctx["device_kind"], **counted)
    return 100.0 * floor_s / found[1]
