#!/usr/bin/env python3
"""The benchmark's command: one cell, one run, one process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic, driver, model builder, reference,
limits and per-layer readers by the names in ``BENCHMARK.json``; prints the
numbers compared on standard error and one JSON object as the last line of
standard output. Exits non-zero, printing no result, without a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def read_per_layer(cell, run: dict) -> dict:
    """Every per-layer metric of the cell through the reader its file
    names; a reader that finds nothing to read returns None and the metric
    stays out of the line."""
    values = {}
    for metric in cell.per_layer:
        spec = cell.metric_file(metric["name"])
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(cell, run, **spec.get("args", {}))
        if value is not None:
            values[metric["name"]] = float(value)
    return values


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, root: Path = ROOT,
             t_start: float = None) -> int:
    from benchmarks.harness import device, manifest, result, window
    if not (root / "analytics_zoo_tpu").is_dir():
        print("benchmark: refused: the system under test is not in this "
              "checkout", file=sys.stderr)
        return 3
    cell = manifest.Cell(workload, root)
    dev = device.require_chips(cell.chips) if require_chip \
        else device.describe()
    listener = window.CompileListener()
    run = cell.driver().run(cell, seed, seconds, trace,
                            T_START if t_start is None else t_start,
                            listener)
    dev = dict(dev, memory_peak_bytes=run["memory_peak_bytes"])
    per_layer = {}
    if trace:
        summary = run["evidence"]["profiler"].summary()
        run["trace"] = summary
        run["peaks"] = device.peaks_for(dev["kind"]) if require_chip else None
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        run["breakdown"] = summary.breakdown()
        per_layer = read_per_layer(cell, run)
    result.emit(cell, trace, run, dev, per_layer)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    # libtpu logs to the fixed path /tmp/tpu_logs unless told otherwise:
    # keep its files under this run's own TMPDIR
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    return run_cell(a.workload, a.seed, a.seconds, bool(a.trace))


if __name__ == "__main__":
    sys.exit(main())
