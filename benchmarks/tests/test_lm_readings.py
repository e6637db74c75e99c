"""``tools/lm_readings.py --rows``: the groups it decodes in, the number
it takes for the control's as a cell's, and one rehearsal of the tool for
each configuration whose limit rests on its readings."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness.manifest import ROOT
from benchmarks.tools.lm_readings import cell_control, same_bucket_groups

BUCKETS = [64, 32, 64, 64, 32, 32, 64, 32, 64, 32, 32, 32, 64, 32, 32, 32,
           64, 32]  # data/seeds.txt's 17 titles and the stand-in


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_every_prompt_is_served_once_in_a_group_of_its_own_bucket(rows):
    groups = same_bucket_groups(BUCKETS, rows)
    kept = [i for group, n in groups for i in group[:n]]
    assert sorted(kept) == list(range(len(BUCKETS)))
    for group, n in groups:
        assert len(group) == rows and 1 <= n <= rows
        assert len({BUCKETS[i] for i in group}) == 1
    # 11 prompts of bucket 32 and 7 of 64: at four rows each bucket's last
    # group is filled from the bucket's start
    assert sum(rows - n for _, n in groups) == {1: 0, 2: 2, 4: 2}[rows]


def test_a_bucket_smaller_than_a_group_repeats_its_own_prompts():
    assert same_bucket_groups([32, 64, 32], 4) == [
        ([0, 2, 0, 2], 2), ([1, 1, 1, 1], 1)]


def test_the_controls_number_as_a_cells_leaves_out_what_a_run_may_not_draw():
    readings = [float(i) for i in range(18)]
    assert cell_control(readings, 16) == 15.0   # the third largest
    assert cell_control(readings, 18) == cell_control(readings, 40) == 17.0


@pytest.mark.parametrize("cell", ["qwen3next_rollover", "lfm2_rollover"])
def test_the_tool_reads_every_prompt_at_every_row_count(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tools",
                                      "lm_readings.py"),
         "--workload", cell, "--seeds", "2147483801", "--rows", "1,2,4",
         "--platform-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rows"] == [1, 2, 4] and set(line["by_rows"]) == {"1", "2",
                                                                  "4"}
    for read in line["by_rows"].values():
        for name in ("program", "control_fp8", "wrong_low"):
            assert len(read["per_prompt"][name]) == line["prompts"] == 18
        # float32 at the tiny size: the program agrees with the reference
        # at every batch shape, the control does not, nor an altered token
        assert read["program"] < 1e-3 < read["cell_control"]
        assert read["wrong_token"][0] > 0.01
    assert line["program"] == max(
        r["program"] for r in line["by_rows"].values())
    assert line["cell_control"] == min(
        r["cell_control"] for r in line["by_rows"].values())
