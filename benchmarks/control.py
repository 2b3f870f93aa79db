#!/usr/bin/env python3
"""Readings for the limits of ``correct``: NOT part of a benchmark run.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 \
        --controls 3 [--seconds 4]

In one process, for every seed: the program's numbers against the plain
reference (the lower readings), and on the first ``--controls`` seeds the
reference computed in the control's precision and with each fault planted,
against the reference (the upper readings). Prints one JSON line a seed.
PERF.md says which readings each limit in ``benchmarks/limits/`` was set
from.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    from benchmarks.harness import device, manifest, window
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    a = ap.parse_args(argv)
    cell = manifest.Cell(a.workload)
    device.require_chips(cell.chips)
    listener = window.CompileListener()
    for k, seed in enumerate(int(s) for s in a.seeds.split(",")):
        r = cell.driver().control_readings(cell, seed, a.seconds,
                                           k < a.controls, listener)
        print(json.dumps({"workload": a.workload, "seed": seed, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
