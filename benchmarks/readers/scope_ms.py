"""Device time by named scope of the compiled step, from the traced run.

A TPU trace names each op event by its HLO instruction and carries no
name stack. The program keeps, for each executable it compiled ahead of
time, a scope index (``analytics_zoo_tpu.common.profiling.scope_index``):
instruction name -> {"scope", "phase", "scopes", "opcode"}. This reader
joins the two on the instruction's name and returns the time in which an
op of the selected scopes ran (a union, so overlapping ops count once),
per optimizer step of the traced stretch in ms or, with ``share``, in
percent of the time in which any op ran. Container ops (a ``while`` with
its body's ops inside it) are left out as ``trace.top_ops`` leaves them
out. A program without a scope index (a parent commit from before it)
gives nothing to read.

Select by one of: ``pattern``, a regular expression searched in the
instruction's ``scope``; ``unscoped``, ops whose instruction the index
does not know or knows no scope for; ``mixed``, a list of patterns: ops
whose ``scopes`` fall under more than one of them.
"""

import re

from benchmarks.harness import trace

_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


def load_index(executable: str):
    try:
        from analytics_zoo_tpu.common import profiling
    except ImportError:
        return None
    lookup = getattr(profiling, "scope_index", None)
    return lookup(executable) if lookup else None


def traced_steps(cell, run):
    """Optimizer steps of the traced stretch."""
    units = run["evidence"].get("traced_units")
    return units / int(cell.traffic["batch_size"]) if units else None


def op_events(run):
    """The first chip's op events cut to the traced window, containers
    left out."""
    t = run["trace"]
    lo, hi = t.window
    events = trace.clip(next(iter(t.devices.values())), t.window)
    return [e for e in events if e[2] < 0.25 * (hi - lo)]


def covered_seconds(events) -> float:
    return trace.covered_ns([(e[1], e[1] + e[2]) for e in events]) / 1e9


def select(events, index, pattern=None, unscoped=False, mixed=None):
    chosen = []
    rx = re.compile(pattern) if pattern else None
    groups = [re.compile(g) for g in mixed or ()]
    for e in events:
        m = _INSTRUCTION.match(e[0])
        entry = index.get(m.group(1)) if m else None
        if unscoped:
            hit = entry is None or not entry["scope"]
        elif entry is None:
            hit = False
        elif groups:
            hit = sum(any(g.search(s) for s in entry["scopes"])
                      for g in groups) > 1
        else:
            hit = bool(entry["scope"]) and bool(rx.search(entry["scope"]))
        if hit:
            chosen.append(e)
    return chosen


def read(cell, run, executable: str, pattern: str = None,
         unscoped: bool = False, mixed: list = None, share: bool = False):
    index = load_index(executable)
    steps = traced_steps(cell, run)
    if not index or not steps:
        return None
    events = op_events(run)
    seconds = covered_seconds(
        select(events, index, pattern, unscoped, mixed))
    if share:
        busy = covered_seconds(events)
        return 100.0 * seconds / busy if busy > 0 else None
    return 1e3 * seconds / steps
