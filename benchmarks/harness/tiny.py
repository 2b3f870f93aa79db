"""A copy of the benchmark's data files at a size the CPU tests can hold:
the same drivers, readers and references, two BERT layers of hidden 32,
short windows. Its ``BENCHMARK.json`` is the real one plus the entries of
``tests/benchmarks/unadmitted_cells.json``, so that the tests also drive
the serving driver, which no admitted cell runs yet. Times from it mean
nothing and are never reported."""

import json
import os
import time
from pathlib import Path

from benchmarks.harness.manifest import ROOT, load_manifest

TINY_CONFIG = {"bert": dict(hidden_size=32, num_attention_heads=4,
                            intermediate_size=64, num_hidden_layers=2,
                            vocab_size=100, max_position_embeddings=16)}
TINY_TRAFFIC = {
    "train_epochs": dict(batch_size=8, steps_per_epoch=4, seq_len=16,
                         reference_block_rows=4, trace_seconds=0.5),
    "serve": dict(seq_len=16, pool=64, warm_seconds=0.5, check_records=32,
                  reference_block_rows=16, outstanding=64, rate_per_s=200.0,
                  engine={"batch_size": 8, "min_batch_size": 8,
                          "max_batch_size": 16}),
}
#: float32 on the CPU agrees with the reference to about 1e-6; the same
#: model in bfloat16 reads about 1e-2
TINY_LIMIT = 1e-3


def make_root(tmp: Path, compute_dtype: str = "float32",
              overrides: dict = None) -> Path:
    """Writes the tiny data files under ``tmp`` and returns it."""
    tmp = Path(tmp)
    bench = tmp / "benchmarks"
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    if not (bench / "metrics").exists():
        os.symlink(ROOT / "benchmarks" / "metrics", bench / "metrics")
    if not (tmp / "analytics_zoo_tpu").exists():
        os.symlink(ROOT / "analytics_zoo_tpu", tmp / "analytics_zoo_tpu")
    manifest = tests_manifest()
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    for entry in manifest["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        cfg.update(TINY_CONFIG[cfg["family"]], compute_dtype=compute_dtype)
        (tmp / entry["file"]).write_text(json.dumps(cfg))
    for cell in manifest["workloads"]:
        src = ROOT / "benchmarks" / "traffic" / f"{cell['traffic']}.json"
        t = json.loads(src.read_text())
        t.update(TINY_TRAFFIC[t["kind"]])
        t.update((overrides or {}).get(cell["name"], {}))
        (bench / "traffic" / src.name).write_text(json.dumps(t))
        lim = json.loads((ROOT / "benchmarks" / "limits"
                          / f"{cell['name']}.json").read_text())
        lim["limits"] = {k: (0 if v == 0 else TINY_LIMIT)
                         for k, v in lim["limits"].items()}
        (bench / "limits" / f"{cell['name']}.json").write_text(
            json.dumps(lim))
    return tmp


def tests_manifest() -> dict:
    """``BENCHMARK.json`` with the unadmitted cells' entries appended."""
    manifest = load_manifest(ROOT)
    extra = json.loads((ROOT / "tests" / "benchmarks"
                        / "unadmitted_cells.json").read_text())
    for key in ("workloads", "end_to_end", "per_layer"):
        manifest[key] = manifest[key] + extra[key]
    return manifest


def full_root(tmp: Path) -> Path:
    """The real data files (a link to ``benchmarks/``) under the tests'
    manifest: a root in which the unadmitted cells resolve too."""
    tmp = Path(tmp)
    os.symlink(ROOT / "benchmarks", tmp / "benchmarks")
    (tmp / "BENCHMARK.json").write_text(json.dumps(tests_manifest()))
    return tmp


def run_cell(capsys, root: Path, cell: str, seed: int, seconds: float):
    """One run of ``cell`` under ``root`` without the look for a chip:
    (exit code, the result line, standard error)."""
    from benchmarks import run as run_mod
    rc = run_mod.run_cell(cell, seed, seconds, False, require_chip=False,
                          root=root, t_start=time.perf_counter())
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


def all_cells(kind: str = None) -> list:
    """Names of the admitted and the unadmitted cells, optionally those of
    one traffic kind."""
    names = []
    for w in tests_manifest()["workloads"]:
        t = json.loads((ROOT / "benchmarks" / "traffic"
                        / f"{w['traffic']}.json").read_text())
        if kind in (None, t["kind"]):
            names.append(w["name"])
    return names
