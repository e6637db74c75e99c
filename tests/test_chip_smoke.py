"""chip_smoke.py's output contract, pinned on the CPU.

The driver decides a bring-up PR by the LAST stdout line of
``python chip_smoke.py`` on the chip; a finished bring-up was once lost
to that line's format alone. These tests run the script's tiny-size
rehearsal path in a subprocess (``--cpu-rehearsal``: test_config models
on the CPU backend, same phases and control flow) and hold the line to
the letter. What the chip run itself proves is not tested here."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


RUNS = {
    # name: (arguments, device count, the phases that must run, in order)
    "one_chip": (("--cpu-rehearsal",), 1,
                 ["start", "build", "scorer", "lm", "image", "server"]),
    "four_chips": (("--cpu-rehearsal", "--chips", "4"), 4,
                   ["start", "image_dp4", "image_one_device", "compare"]),
    "no_override": ((), None, None),
}


@pytest.fixture(scope="module")
def runs():
    """The three invocations, started together (each is mostly its own
    jax import and tiny compiles, and the tier-1 window is tight)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = {
        name: subprocess.Popen(
            [sys.executable, SMOKE, *args], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env, cwd=REPO)
        for name, (args, _, _) in RUNS.items()}
    done = {}
    try:
        for name, proc in procs.items():
            out, err = proc.communicate(timeout=600)
            done[name] = (proc.returncode, out, err)
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
    return done


@pytest.mark.parametrize("name", ["one_chip", "four_chips"])
def test_last_stdout_line_is_the_result(runs, name):
    _, count, phases = RUNS[name]
    returncode, stdout, stderr = runs[name]
    assert returncode == 0, stderr[-3000:]
    # every stdout line is one of the script's own JSON objects: nothing
    # else in the process (aiohttp, loggers, teardown) may reach stdout
    lines = [json.loads(line) for line in stdout.splitlines()]
    assert stdout.endswith("\n") and stdout.count("\n") == len(lines), \
        "something besides whole JSON lines is on stdout"
    result = lines[-1]
    assert set(result) == {"ok", "device"}
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["ok"] is True
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": count}
    # --chips 4 runs the sharded path and its comparison, nothing else
    assert [line["phase"] for line in lines[:-1]] == phases
    for line in lines[1:-1]:
        assert line["ok"] is True
        assert {"seconds", "compile_seconds", "jit_cache_hits",
                "jit_cache_misses", "peak_bytes_in_use"} <= set(line)


def test_no_tpu_and_no_override_fails_without_a_result(runs):
    """Without the test-only override a host with no TPU gets a non-zero
    exit and NO result line: the smoke never carries on on the CPU."""
    returncode, stdout, stderr = runs["no_override"]
    assert returncode != 0
    assert stdout == ""
    assert "no TPU" in stderr
