"""Bench regression sentinel (tools/bench_diff.py, ISSUE 14): verdict
grammar (regression / improvement / within-noise / missing / error /
skipped / new), per-entry noise tolerances, diagnosis counter-delta
surfacing, direction-by-unit, and the CLI exit-code acceptance
contract. Fast tier; stdlib-only module, no jax."""

import json
import os
import subprocess
import sys

import pytest

from tools.bench_diff import (
    DEFAULT_TOLERANCE,
    diff_entry,
    diff_suites,
    format_table,
    higher_is_better,
    main,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _entry(value, unit="images/sec", **extra):
    return {"metric": "m", "value": value, "unit": unit, **extra}


# -- verdict grammar --------------------------------------------------------

def test_regression_beyond_tolerance_flags():
    row = diff_entry("sd15", _entry(1.0), _entry(0.8))
    assert row["verdict"] == "regression"
    assert row["change_pct"] == pytest.approx(-20.0)


def test_improvement_beyond_tolerance():
    row = diff_entry("sd15", _entry(1.0), _entry(1.3))
    assert row["verdict"] == "improvement"


def test_within_noise_band():
    assert diff_entry("sd15", _entry(1.0),
                      _entry(1.05))["verdict"] == "within_noise"
    assert diff_entry("sd15", _entry(1.0),
                      _entry(0.95))["verdict"] == "within_noise"


def test_missing_entry_flags():
    row = diff_entry("sd15", _entry(1.0), None)
    assert row["verdict"] == "missing"


def test_fresh_error_over_measured_baseline_flags():
    row = diff_entry("sd15", _entry(1.0), {"error": "device died"})
    assert row["verdict"] == "error"
    assert "device died" in row["error"]


def test_pending_hardware_baseline_skipped():
    """The pending-hardware annotations (gpt2_spec & co) are baseline
    entries with an error field: nothing to regress against — both on
    an identical fresh copy and when the fresh run also errors."""
    pending = {"metric": "m", "error": "pending hardware window"}
    assert diff_entry("gpt2_spec", pending,
                      pending)["verdict"] == "skipped"
    assert diff_entry("gpt2_spec", pending, None)["verdict"] == "skipped"


def test_new_entry_is_informational():
    assert diff_entry("fresh_only", None, _entry(2.0))["verdict"] == "new"


# -- direction by unit ------------------------------------------------------

def test_seconds_units_are_lower_better():
    assert not higher_is_better({"unit": "seconds"})
    assert higher_is_better({"unit": "tokens/sec"})
    assert higher_is_better({"unit": "accepted req/s"})
    # latency REGRESSION = value going UP
    row = diff_entry("e2e", _entry(1.0, unit="seconds"),
                     _entry(1.4, unit="seconds"))
    assert row["verdict"] == "regression"
    row = diff_entry("e2e", _entry(1.0, unit="seconds"),
                     _entry(0.7, unit="seconds"))
    assert row["verdict"] == "improvement"


# -- tolerances carried per entry -------------------------------------------

def test_per_entry_tolerance_overrides_default():
    base = _entry(1.0, noise_tolerance=0.3)
    assert diff_entry("noisy", base, _entry(0.75))["verdict"] \
        == "within_noise"
    # the fresh record's tolerance wins over the baseline's
    row = diff_entry("noisy", base, _entry(0.75, noise_tolerance=0.05))
    assert row["verdict"] == "regression"
    assert diff_entry("tight", _entry(1.0),
                      _entry(0.8))["verdict"] == "regression"
    assert DEFAULT_TOLERANCE == pytest.approx(0.10)


# -- diagnosis counter deltas -----------------------------------------------

def test_regression_surfaces_counter_delta_changes():
    base = _entry(1.0, counter_deltas={"jit.compiles": 40})
    fresh = _entry(0.7, counter_deltas={"jit.compiles": 40,
                                        "jit.recompiles": 900})
    row = diff_entry("sd15", base, fresh)
    assert row["verdict"] == "regression"
    changes = row["counter_delta_changes"]
    assert changes == {"jit.recompiles": {"baseline": None,
                                          "fresh": 900}}
    table = format_table([row])
    assert "jit.recompiles" in table and "900" in table


def test_within_noise_carries_no_diagnosis():
    base = _entry(1.0, counter_deltas={"jit.compiles": 40})
    fresh = _entry(0.99, counter_deltas={"jit.compiles": 41})
    assert "counter_delta_changes" not in diff_entry("sd15", base, fresh)


# -- suite-level diff -------------------------------------------------------

def test_diff_suites_covers_union_and_restriction():
    base = {"a": _entry(1.0), "b": _entry(2.0)}
    fresh = {"a": _entry(1.0), "c": _entry(3.0)}
    rows = {r["entry"]: r["verdict"] for r in diff_suites(base, fresh)}
    assert rows == {"a": "within_noise", "b": "missing", "c": "new"}
    only = diff_suites(base, fresh, entries=["a"])
    assert [r["entry"] for r in only] == ["a"]


# -- CLI acceptance contract ------------------------------------------------

def _write(tmp_path, name, data):
    path = tmp_path / name
    path.write_text(json.dumps(data))
    return str(path)


def test_cli_unmodified_committed_suite_exits_zero(capsys):
    """The acceptance bar: bench_diff against an unmodified copy of the
    committed BENCH_SUITE.json exits 0."""
    assert main([os.path.join(REPO, "BENCH_SUITE.json")]) == 0
    out = capsys.readouterr().out
    assert "within_noise" in out


def test_cli_degraded_entry_exits_nonzero_naming_it(tmp_path, capsys):
    """...and against a copy with one entry's throughput degraded 20%
    exits nonzero NAMING that entry."""
    with open(os.path.join(REPO, "BENCH_SUITE.json")) as f:
        suite = json.load(f)
    suite["sd15"]["value"] = round(suite["sd15"]["value"] * 0.8, 4)
    fresh = _write(tmp_path, "degraded.json", suite)
    assert main([fresh]) == 1
    captured = capsys.readouterr()
    assert "sd15" in captured.err and "regression" in captured.err


def test_cli_entry_mode_accepts_records_with_dict_fields(tmp_path,
                                                         capsys):
    """A real bench.py --entry record carries dict-valued fields
    (counter_deltas — the diagnosis data this tool exists for); the
    single-record detection must not misread it as a suite mapping
    (which would verdict every healthy run 'missing')."""
    base = _write(tmp_path, "base.json", {"sd15": _entry(1.0)})
    single = _write(tmp_path, "single.json",
                    _entry(1.0, counter_deltas={"jit.compiles": 12},
                           cpu_smoke={"value": 0.5}))
    assert main([single, "--baseline", base, "--entry", "sd15"]) == 0
    assert "within_noise" in capsys.readouterr().out


def test_cli_entry_mode_places_single_record(tmp_path, capsys):
    base = _write(tmp_path, "base.json",
                  {"sd15": _entry(1.0), "gpt2": _entry(500.0,
                                                       unit="tokens/sec")})
    single = _write(tmp_path, "single.json", _entry(0.5))
    rc = main([single, "--baseline", base, "--entry", "sd15"])
    assert rc == 1
    assert "sd15" in capsys.readouterr().err
    # a single record without --entry is a usage error
    with pytest.raises(SystemExit):
        main([single, "--baseline", base])
    # --entry restriction: the OTHER entries are not "missing"
    ok = _write(tmp_path, "ok.json", _entry(1.0))
    assert main([ok, "--baseline", base, "--entry", "sd15"]) == 0


def test_cli_json_output(tmp_path, capsys):
    base = _write(tmp_path, "base.json", {"a": _entry(1.0)})
    fresh = _write(tmp_path, "fresh.json", {"a": _entry(1.0)})
    assert main([fresh, "--baseline", base, "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert rows[0]["verdict"] == "within_noise"


def test_cli_subprocess_against_committed_suite():
    """The exact invocation the acceptance criteria name, as a child
    process (exit code is the contract CI keys on)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_diff.py"),
         os.path.join(REPO, "BENCH_SUITE.json")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
