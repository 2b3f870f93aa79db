"""Device time of a named scope's kernel launches, or of the work around
them, from the traced run.

The program's scope index (``profiling.scope_index``) names, on the entry
of a custom call that launches a known kernel, the kernel: ``kernel`` is
a pallas kernel's label (``flash_fwd``, ``norm_rotary_bwd`` ...) or
``ragged_dot`` for the compiler's grouped matrix product, and a flash
launch's entry carries ``tiles``, the grid steps its launch lists. This
reader takes the ops of the scopes ``pattern`` matches, as ``scope_ms``
selects them, keeps those whose ``kernel`` is one of ``kernels`` — or,
with ``exclude``, every other op of those scopes — and returns the time
in which one of them ran (a union): per optimizer step of the traced
stretch in ms (``per: step``), or per grid step of the kept launches that
ran in the stretch in us (``per: tile``). An index that names no kernel
anywhere (a program from before the key) gives nothing to read.
"""

import re

from benchmarks.readers import scope_ms

_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


def _entry(index, event) -> dict:
    m = _INSTRUCTION.match(event[0])
    return index.get(m.group(1), {}) if m else {}


def read(cell, run, executable: str, pattern: str, kernels: list,
         exclude: bool = False, per: str = "step"):
    index = scope_ms.load_index(executable)
    steps = scope_ms.traced_steps(cell, run)
    if not index or not steps \
            or not any("kernel" in e for e in index.values()):
        return None
    wanted = set(kernels)
    kept = [e for e in scope_ms.select(scope_ms.op_events(run), index,
                                       pattern)
            if (_entry(index, e).get("kernel") in wanted) != exclude]
    seconds = scope_ms.covered_seconds(kept)
    if per == "tile":
        tiles = sum(_entry(index, e).get("tiles", 0) for e in kept)
        return 1e6 * seconds / tiles if tiles else None
    return 1e3 * seconds / steps
