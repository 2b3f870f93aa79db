"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here names a cell, a configuration or a metric: a later PR adds a
cell by adding entries to ``BENCHMARK.json`` and files under
``benchmarks/`` (see ``PERF.md``, "adding a cell").
"""

import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"


def load_manifest(root: Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def metric_cells(metric: dict, manifest: dict, kind: str) -> list:
    """The cells a metric is reported in: its ``workloads`` key, or every
    cell (end-to-end) / every cell that reports what it ``moves``."""
    if "workloads" in metric:
        return list(metric["workloads"])
    names = [w["name"] for w in manifest["workloads"]]
    if kind == "end_to_end":
        return names
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == metric["moves"])
    return metric_cells(moved, manifest, "end_to_end")


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = root
        self.manifest = load_manifest(root)
        found = [w for w in self.manifest["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.workload = found[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = next(c for c in self.manifest["configs"]
                     if c["name"] == self.workload["config"])
        self.config = _read_json(root / entry["file"])
        self.traffic = _read_json(
            root / "benchmarks" / "traffic"
            / f"{self.workload['traffic']}.json")
        limits = root / "benchmarks" / "limits" / f"{name}.json"
        self.limits = _read_json(limits)["limits"] if limits.exists() else {}
        self.end_to_end = [
            m for m in self.manifest["end_to_end"]
            if name in metric_cells(m, self.manifest, "end_to_end")]
        self.per_layer = [
            m for m in self.manifest["per_layer"]
            if name in metric_cells(m, self.manifest, "per_layer")]

    @property
    def family(self) -> str:
        return self.config["family"]

    def module(self, package: str, name: str = None):
        return importlib.import_module(
            f"benchmarks.{package}.{name or self.family}")

    def driver(self):
        return self.module("drivers", self.traffic["kind"])

    def metric_file(self, metric_name: str) -> dict:
        return _read_json(self.root / "benchmarks" / "metrics"
                          / f"{metric_name}.json")
