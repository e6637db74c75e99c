"""Find a cell's files by the names in BENCHMARK.json."""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def resolve(target: str):
    """The object a file names as ``module:attribute``."""
    module, _, attr = target.partition(":")
    return getattr(importlib.import_module(module), attr)


def load_manifest() -> dict:
    return load_json("BENCHMARK.json")


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and
    the metrics it reports."""

    def __init__(self, manifest: dict, name: str) -> None:
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in manifest["configs"]
                         if c["name"] == self.entry["config"])
        self.config = load_json(cfg_entry["file"])
        self.traffic = load_json("benchmarks", "traffic",
                                 self.entry["traffic"] + ".json")
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]

    def reader_spec(self, metric: str) -> dict:
        return load_json("benchmarks", "layer_metrics", metric + ".json")
