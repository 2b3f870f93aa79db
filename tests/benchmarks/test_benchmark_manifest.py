"""BENCHMARK.json against its own rules and against the files it names."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ADMITTED = json.loads((ROOT / "BENCHMARK.json").read_text())
UNADMITTED = json.loads(
    (ROOT / "tests" / "benchmarks" / "unadmitted_cells.json").read_text())
# the rules hold for the admitted cells and for the unadmitted alike
MANIFEST = dict(ADMITTED, **{
    k: ADMITTED[k] + UNADMITTED[k]
    for k in ("workloads", "end_to_end", "per_layer")})
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]


def _cells_of(metric, kind):
    from benchmarks.harness.manifest import metric_cells
    return metric_cells(metric, MANIFEST, kind)


def test_top_level_keys_and_sizes():
    assert set(ADMITTED) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    n = len(ADMITTED["workloads"])
    assert 1 <= n <= 24
    # a full check has to fit even with the full 24 cells
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) \
        <= max(1, n // 4)
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    for path in MANIFEST["paths"]:
        assert (ROOT / path).is_dir()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist(cell, tmp_path):
    from benchmarks.harness import tiny
    from benchmarks.harness.manifest import Cell
    c = Cell(cell, tiny.full_root(tmp_path))
    assert NAME.match(cell) and NAME.match(c.workload["traffic"])
    assert c.chips in (1, 4) and len(c.workload["why"]) <= 200
    assert set(c.workload) == {"name", "config", "traffic", "chips", "why"}
    bench = ROOT / "benchmarks"
    assert (bench / "drivers" / f"{c.traffic['kind']}.py").exists()
    for package in ("models", "references", "flops"):
        assert (bench / package / f"{c.family}.py").exists()
    assert c.limits, "a cell needs its limits file"
    reported = [m["name"] for m in c.end_to_end]
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer, "a cell reports at least one per-layer metric"


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_entry(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config)
    assert any(entry["file"].startswith(p + "/") for p in MANIFEST["paths"])
    body = json.loads((ROOT / entry["file"]).read_text())
    assert body["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size)$",
                             key), f"{key} is a width"
    assert any(w["config"] == config for w in ADMITTED["workloads"])


@pytest.mark.parametrize("metric", list(E2E))
def test_end_to_end_metric(metric):
    m = E2E[metric]
    assert NAME.match(metric) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    for cell in _cells_of(m, "end_to_end"):
        assert cell in CELLS


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    assert NAME.match(metric) and UNIT.match(m["unit"])
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert m["moves"] in E2E and m["moves"] != "setup_s"
    moved = set(_cells_of(E2E[m["moves"]], "end_to_end"))
    cells = _cells_of(m, "per_layer")
    assert cells and set(cells) <= moved
    spec = json.loads((ROOT / "benchmarks" / "metrics"
                       / f"{metric}.json").read_text())
    assert (ROOT / "benchmarks" / "readers"
            / f"{spec['reader']}.py").exists()
    if metric.endswith("_roofline") or "_roofline." in metric \
            or "mfu" in metric:
        assert m["unit"] == "%"


def test_names_are_unique_and_layers_listed_in_perf_md():
    names = list(E2E) + PER_LAYER
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    perf = (ROOT / "PERF.md").read_text()
    for layer in {m["layer"] for m in MANIFEST["per_layer"]}:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_run_py_names_no_cell_config_or_metric():
    text = (ROOT / "benchmarks" / "run.py").read_text()
    for name in CELLS + list(E2E) + PER_LAYER \
            + [c["name"] for c in MANIFEST["configs"]]:
        assert name not in text, f"run.py names {name!r}"


def test_every_file_under_paths_has_an_allowed_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        for f in (ROOT / path).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert ok.match(str(f.relative_to(ROOT))), f
