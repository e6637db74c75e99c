"""Bench suite harness resilience (a device call that hangs mid-suite
would hang an in-process entry forever and lose every number). Entries
run in per-entry subprocesses with wall-clock timeouts; a hung or
failing entry becomes a clean error record carrying its own error, and
the suite moves on."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def test_suite_survives_hung_entry(tmp_path):
    """With a 3s entry budget, the scorer entry (which needs ~2min on
    CPU) times out — the suite records the timeout as data instead of
    hanging, and exits cleanly because the north star wasn't asked
    for."""
    suite_path = str(tmp_path / "BENCH_SUITE.json")
    env = dict(os.environ, BENCH_SUITE_ENTRIES="scorer",
               BENCH_ENTRY_TIMEOUT="3", BENCH_SUITE_PATH=suite_path)
    proc = subprocess.run(
        [sys.executable, BENCH, "--suite", "--platform-cpu"],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    results = json.load(open(suite_path))
    assert "timeout" in results["scorer"]["error"]
    assert "measured_at" in results["scorer"]


def test_suite_error_never_clobbers_prior_success(tmp_path):
    """Merge semantics (the round 1-3 failure mode: a mid-suite outage
    zeroed whole runs): a fresh ERROR keeps the previously-measured
    success; a fresh success overwrites; and the file is rewritten
    per-entry, not at suite end."""
    suite_path = str(tmp_path / "BENCH_SUITE.json")
    prior = {"scorer": {"metric": "scorer", "value": 3702.4,
                        "unit": "pairs/sec",
                        "measured_at": "2026-07-01T00:00:00Z"}}
    json.dump(prior, open(suite_path, "w"))
    env = dict(os.environ, BENCH_SUITE_ENTRIES="scorer",
               BENCH_ENTRY_TIMEOUT="3", BENCH_SUITE_PATH=suite_path)
    proc = subprocess.run(
        [sys.executable, BENCH, "--suite", "--platform-cpu"],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    results = json.load(open(suite_path))
    # the timeout error must NOT have replaced the measured number
    assert results["scorer"]["value"] == 3702.4
    assert "error" not in results["scorer"]
    assert "keeping prior measurement" in proc.stderr


def test_suite_persists_each_entry_as_it_lands(tmp_path, monkeypatch):
    """The suite file must exist with entry 1's result BEFORE entry 2
    runs — verified by having entry 2's (fake) runner read the file."""
    bench = _import_bench()
    suite_path = str(tmp_path / "BENCH_SUITE.json")
    seen_at_entry2 = {}

    def fake_isolated(name, weights_dir, timeout_s, cpu=False):
        if name == "gpt2" and os.path.exists(suite_path):
            seen_at_entry2.update(json.load(open(suite_path)))
        return {"metric": name, "value": 1.0}

    monkeypatch.setattr(bench, "_run_entry_isolated", fake_isolated)
    monkeypatch.setenv("BENCH_SUITE_PATH", suite_path)
    monkeypatch.setenv("BENCH_SUITE_ENTRIES", "scorer,gpt2")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--suite",
                                      "--platform-cpu"])
    bench.main()
    assert seen_at_entry2["scorer"]["value"] == 1.0
    final = json.load(open(suite_path))
    assert set(final) == {"scorer", "gpt2"}


def test_fresh_north_star_failure_exits_nonzero(tmp_path, monkeypatch):
    """When sd15 fails THIS run, the suite must exit non-zero even
    though the file keeps a prior measurement — callers keying on the
    exit code must never mistake a stale number for a fresh run."""
    bench = _import_bench()
    suite_path = str(tmp_path / "BENCH_SUITE.json")
    with open(suite_path, "w") as f:
        json.dump({"sd15": {"metric": "sd15", "value": 1.19,
                            "measured_at": "2026-06-01T00:00:00Z"}}, f)
    monkeypatch.setattr(
        bench, "_run_entry_isolated",
        lambda name, w, t, cpu=False: {"metric": name,
                                       "error": "device died"})
    monkeypatch.setenv("BENCH_SUITE_PATH", suite_path)
    monkeypatch.setenv("BENCH_SUITE_ENTRIES", "sd15")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--suite",
                                      "--platform-cpu"])
    try:
        bench.main()
        raise AssertionError("suite should have exited non-zero")
    except SystemExit as e:
        assert "north-star bench failed" in str(e)
    # ...but the file still holds the prior hardware evidence
    assert json.load(open(suite_path))["sd15"]["value"] == 1.19


def test_north_star_only_runs_fast_path(tmp_path, monkeypatch):
    """--north-star-only runs exactly NORTH_STAR_ENTRIES (sd15 first)
    at 1 timed round unless the caller pinned a rep count."""
    bench = _import_bench()
    suite_path = str(tmp_path / "BENCH_SUITE.json")
    ran = []

    def fake_isolated(name, weights_dir, timeout_s, cpu=False):
        ran.append((name, os.environ.get("BENCH_ROUNDS")))
        return {"metric": name, "value": 2.0}

    monkeypatch.setattr(bench, "_run_entry_isolated", fake_isolated)
    monkeypatch.setenv("BENCH_SUITE_PATH", suite_path)
    monkeypatch.delenv("BENCH_ROUNDS", raising=False)
    monkeypatch.delenv("BENCH_SUITE_ENTRIES", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--north-star-only",
                                      "--platform-cpu"])
    bench.main()
    assert [n for n, _ in ran] == list(bench.NORTH_STAR_ENTRIES)
    assert ran[0] == ("sd15", "1")  # children inherit the 1-rep env
    assert set(json.load(open(suite_path))) == set(bench.NORTH_STAR_ENTRIES)


def test_suite_order_is_north_star_first():
    """Suites get cut short: sd15 and sd15_fast must be the first two
    entries so a partial run still lands the perf-case numbers."""
    bench = _import_bench()
    assert list(bench.SUITE)[:2] == ["sd15", "sd15_fast"]


def test_kept_prior_is_annotated_with_fresh_error(tmp_path, monkeypatch):
    """When a fresh error keeps a prior success, the persisted record
    must say this run failed (last_error/last_error_at), and the
    per-entry stderr JSON stream must carry the fresh error — not
    reprint the old success as if re-measured."""
    bench = _import_bench()
    suite_path = str(tmp_path / "BENCH_SUITE.json")
    with open(suite_path, "w") as f:
        json.dump({"scorer": {"metric": "scorer", "value": 3702.4,
                              "measured_at": "2026-07-01T00:00:00Z"}}, f)
    monkeypatch.setattr(
        bench, "_run_entry_isolated",
        lambda name, w, t, cpu=False: {"metric": name,
                                       "error": "device died"})
    monkeypatch.setenv("BENCH_SUITE_PATH", suite_path)
    monkeypatch.setenv("BENCH_SUITE_ENTRIES", "scorer")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--suite",
                                      "--platform-cpu"])
    bench.main()
    rec = json.load(open(suite_path))["scorer"]
    assert rec["value"] == 3702.4          # evidence kept
    assert rec["last_error"] == "device died"
    assert "last_error_at" in rec and "error" not in rec


def test_persist_merges_concurrent_writers(tmp_path, monkeypatch):
    """Two suite runs sharing one BENCH_SUITE.json must not drop each
    other's entries: persist re-reads the file at write time, so an
    entry another run landed mid-flight survives our write."""
    bench = _import_bench()
    suite_path = str(tmp_path / "BENCH_SUITE.json")

    def fake_isolated(name, weights_dir, timeout_s, cpu=False):
        # simulate a concurrent --north-star-only run landing sd15
        # while our run is measuring the scorer
        with open(suite_path, "w") as f:
            json.dump({"sd15": {"metric": "sd15", "value": 1.8}}, f)
        return {"metric": name, "value": 3000.0}

    monkeypatch.setattr(bench, "_run_entry_isolated", fake_isolated)
    monkeypatch.setenv("BENCH_SUITE_PATH", suite_path)
    monkeypatch.setenv("BENCH_SUITE_ENTRIES", "scorer")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--suite",
                                      "--platform-cpu"])
    bench.main()
    final = json.load(open(suite_path))
    assert final["sd15"]["value"] == 1.8       # concurrent entry kept
    assert final["scorer"]["value"] == 3000.0  # ours landed too


class _FakeCompleted:
    def __init__(self, rc, stderr="", stdout=""):
        self.returncode = rc
        self.stderr = stderr
        self.stdout = stdout


def _import_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_failing_child_is_reported_with_its_own_error(monkeypatch):
    """A child that exits non-zero — for a missing file or for a kernel
    the compiler refused alike — is run ONCE and its own stderr is the
    record: no second attempt on another code path."""
    bench = _import_bench()
    calls = []

    def fake_run(cmd, timeout, capture_output, text, **kw):
        calls.append(kw)
        return _FakeCompleted(
            1, stderr="MosaicError: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(subprocess, "run", fake_run)
    res = bench._run_entry_isolated("sd15", "weights", timeout_s=300.0)
    assert len(calls) == 1
    assert "env" not in calls[0], "the child runs in the parent's env"
    assert "MosaicError" in res["error"]
    assert "value" not in res


def test_hung_child_is_reported_with_its_own_error(monkeypatch):
    """A wall-clock timeout is reported as a timeout, with what the
    child said before the kill, and is never run again."""
    bench = _import_bench()
    calls = []

    def fake_run(cmd, timeout, capture_output, text, **kw):
        calls.append(1)
        raise subprocess.TimeoutExpired(cmd, timeout,
                                        stderr=b"last words of the child")
    monkeypatch.setattr(subprocess, "run", fake_run)
    res = bench._run_entry_isolated("sd15", "weights", timeout_s=300.0)
    assert len(calls) == 1
    assert "timeout" in res["error"]
    assert "last words" in res["stderr_tail"]


def test_unknown_entry_rejected():
    proc = subprocess.run(
        [sys.executable, BENCH, "--entry", "nope", "--platform-cpu"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "unknown suite entry" in proc.stderr


def test_device_entry_without_a_tpu_fails():
    """The chip is there or the entry fails: a device-bound entry run
    without --platform-cpu on a host with no TPU exits non-zero before
    building anything, and prints no result a reader could mistake for
    a device number."""
    proc = subprocess.run(
        [sys.executable, BENCH, "--entry", "scorer"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout == ""
