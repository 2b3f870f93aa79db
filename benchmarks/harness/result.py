"""Prints a run's result: the numbers compared on standard error, the one
contract line last on standard output."""

import json
import sys


def emit(cell, trace: bool, run: dict, device: dict, per_layer: dict):
    """``run``: what a driver returns. ``per_layer``: name -> value for the
    readers that found something to read."""
    checks = run["checks"]
    if trace:
        wanted = {m["name"]: m["unit"] for m in cell.per_layer}
        values = per_layer
    else:
        wanted = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = run["end_to_end"]
    metrics = {name: {"value": values[name], "unit": unit}
               for name, unit in wanted.items() if values.get(name) is not None}
    line = {"correct": checks.correct, "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics,
            "device": device}
    if trace and run.get("breakdown"):
        line["breakdown"] = run["breakdown"]
    line["compared"] = checks.as_dict()
    for note in run.get("notes", []):
        print(note, flush=True)
    sys.stdout.flush()
    for text in checks.lines():
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
