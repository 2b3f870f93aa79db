"""Each driver end to end on the CPU at the tiny preset: agrees with its
plain reference; the same model in a lower precision does not. These
tests skip the harness's look for a chip and drive the rest of a run."""

import os
import subprocess
import sys

import pytest

from benchmarks.harness import tiny
from benchmarks.harness.manifest import ROOT

CELLS = tiny.all_cells()


def _run(capsys, root, cell):
    return tiny.run_cell(capsys, root, cell, 2 ** 31 + 5, 0.6)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_agrees_with_reference(cell, tmp_path, capsys):
    rc, line, err = _run(capsys, tiny.make_root(tmp_path), cell)
    assert rc == 0 and line["correct"] is True, (line, err)
    assert list(line)[-1] == "compared" and line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for name, c in line["compared"].items():
        assert f"compared {name}:" in err


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_fails_the_comparison(cell, tmp_path, capsys):
    root = tiny.make_root(tmp_path, compute_dtype="bfloat16")
    rc, line, err = _run(capsys, root, cell)
    assert rc == 0 and line["correct"] is False, line
    assert "FAILED" in err


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120)
    assert r.returncode != 0
    assert "metrics" not in r.stdout and "value" not in r.stdout
    assert "refused" in r.stderr
