"""The device as JAX reports it, the peaks table, and the refusal to run
anywhere else."""

import json
import sys
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def load_peaks() -> dict:
    with open(PEAKS_FILE) as f:
        return json.load(f)


def peaks_for(kind: str) -> dict:
    table = load_peaks()
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in {PEAKS_FILE.name}: "
                       f"{sorted(table)}")
    return table[kind]


def describe() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    """Exit non-zero, printing no result, unless JAX holds at least ``chips``
    TPU chips of a kind the peaks table knows."""
    dev = describe()
    if dev["platform"] != "tpu":
        print(f"benchmark: refused: needs a TPU, JAX reports "
              f"{dev['platform']!r} ({dev['kind']})", file=sys.stderr)
        sys.exit(3)
    if dev["count"] < chips:
        print(f"benchmark: refused: the cell needs {chips} chips, JAX "
              f"reports {dev['count']}", file=sys.stderr)
        sys.exit(3)
    try:
        peaks_for(dev["kind"])
    except KeyError as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        sys.exit(3)
    return dev


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes on the fullest of the chips used: the allocator's peak
    of live buffers plus the peak it reserved for programs' temporaries.
    (On a v5e ``peak_bytes_in_use`` alone leaves the temporaries out: a
    program with 1.076 GB of them moved ``peak_bytes_reserved`` by that
    much and ``peak_bytes_in_use`` not at all — my chip run, PR 26.)
    0 where the backend reports nothing, as the CPU does."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak
