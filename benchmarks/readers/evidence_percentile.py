"""A percentile over all the window's samples of a list the driver kept."""

from benchmarks.harness.window import percentile


def read(cell, run, key: str, q: float):
    values = run["evidence"].get(key)
    return percentile(values, q) if values else None
