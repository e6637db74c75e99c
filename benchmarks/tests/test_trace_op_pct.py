"""``trace_op_pct`` on a reduced trace written by hand: the named
operation's share of busy time; nothing where the name, or the trace, is
absent (the parent of the PR that named the kernel has no such name)."""

import pytest

from benchmarks.readers import trace_op_pct

TRACE = {"busy_s": 0.9656, "window_s": 0.9661, "idle_share": 0.0005,
         "device_ops": [["flash_attention", 0.3107], ["fusion", 0.296],
                        ["convert_multiply_fusion", 0.169]]}


@pytest.mark.parametrize("ctx, op, want", [
    ({"trace": TRACE}, "flash_attention", 100 * 0.3107 / 0.9656),
    ({"trace": TRACE}, "fusion", 100 * 0.296 / 0.9656),
    ({"trace": TRACE}, "_flash_bhsd", None),        # named otherwise
    ({"trace": {}}, "flash_attention", None),       # nothing ran
    ({"trace": dict(TRACE, busy_s=0.0)}, "flash_attention", None),
    ({}, "flash_attention", None),                  # --trace 0
], ids=["present", "another", "absent", "empty_trace", "never_busy",
        "no_trace"])
def test_share_of_busy_time_or_nothing(ctx, op, want):
    got = trace_op_pct.read(ctx, {"op": op})
    assert got == (pytest.approx(want) if want is not None else None)
