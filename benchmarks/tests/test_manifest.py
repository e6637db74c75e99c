"""Lint of BENCHMARK.json against the contract and the benchmark's files."""

import os
import re

import pytest

from benchmarks.harness.manifest import ROOT, Cell, load_manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = load_manifest()


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 65536
    # a full check has to fit with the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_names_units_and_lines():
    names = [c["name"] for c in M["configs"]] + [
        w["name"] for w in M["workloads"]] + [
        w["traffic"] for w in M["workloads"]] + [
        m["name"] for m in M["end_to_end"] + M["per_layer"]]
    for name in names:
        assert NAME.match(name), name
    for group in (M["configs"], M["workloads"], M["end_to_end"],
                  M["per_layer"]):
        got = [e["name"] for e in group]
        assert len(got) == len(set(got))
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for text in [w["why"] for w in M["workloads"]] + [
            c["why"] for c in M["configs"]] + [
            c["source"] for c in M["configs"]] + [
            m["layer"] for m in M["per_layer"]] + M["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_cells_pair_once_and_configs_are_used():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in M["workloads"]} == {
        c["name"] for c in M["configs"]}
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    assert any(m["name"] == "setup_s" for m in M["end_to_end"])


@pytest.mark.parametrize("cell_name", [w["name"] for w in M["workloads"]])
def test_cell_files_and_metrics(cell_name):
    cell = Cell(M, cell_name)  # opens the config and the traffic file
    assert cell.config["limits"] and cell.config["sizes"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for metric in cell.per_layer:
        assert metric["moves"] in e2e, metric
        spec = cell.reader_spec(metric["name"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "readers", spec["reader"] + ".py"))
    for path in M["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert os.path.relpath(
        os.path.join(ROOT, next(c["file"] for c in M["configs"]
                                if c["name"] == cell.entry["config"])),
        ROOT).startswith(tuple(M["paths"]))
