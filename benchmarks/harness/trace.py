"""Reduction of a profiler trace to busy time, idle gaps, per-scope time.

Two halves. ``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists; everything after it works on those lists, so the tests
drive it with hand-made intervals and with a cut of a recorded v5e trace
(``benchmarks/fixtures``).

An op event is ``(name, start_ns, duration_ns, scope)``; ``scope`` is the
text of the event's string stats (on a TPU the name stack of the op that
made it, e.g. ``jit(step_fn)/.../block_3/attention/...``). A host span is
``(name, start_ns, duration_ns)`` of a ``TraceAnnotation`` whose name starts
with ``bench:``; both are on the profiler's one clock.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench:"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, device_plane=DEVICE_PLANE, op_line: str = OP_LINE):
    """Returns ({device ordinal: [op events]}, [host spans])."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = device_plane.match(plane.name)
        for line in plane.lines:
            if m and line.name != op_line:
                continue
            for ev in line.events:
                if m:
                    scope = " ".join(str(v) for _, v in ev.stats
                                     if isinstance(v, str))
                    devices.setdefault(int(m.group(1)), []).append(
                        (ev.name, ev.start_ns, ev.duration_ns, scope))
                elif ev.name.startswith(HOST_PREFIX):
                    host.append((ev.name[len(HOST_PREFIX):], ev.start_ns,
                                 ev.duration_ns))
    return devices, host


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def covered_ns(intervals) -> float:
    return sum(end - start for start, end in union(intervals))


def _spans(events):
    return [(e[1], e[1] + e[2]) for e in events]


def clip(events, window):
    """Events cut to the [start, end) window, dropping those outside."""
    lo, hi = window
    out = []
    for name, start, dur, *rest in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s, *rest))
    return out


def busy_seconds(events, window) -> float:
    """Seconds of the window in which at least one op ran."""
    return covered_ns(_spans(clip(events, window))) / 1e9


def scope_seconds(events, window, pattern: str) -> float:
    """Seconds of the window in which an op whose scope or name matches
    the regular expression ran (a union, so nested events count once)."""
    rx = re.compile(pattern)
    hits = [e for e in clip(events, window)
            if rx.search(e[3]) or rx.search(e[0])]
    return covered_ns(_spans(hits)) / 1e9


def idle_gaps(events, window):
    """[(start_ns, end_ns)] of the window in which no op ran."""
    lo, hi = window
    gaps, at = [], lo
    for start, end in union(_spans(clip(events, window))):
        if start > at:
            gaps.append((at, start))
        at = max(at, end)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def attribute_gaps(gaps, host_spans, top: int = 10):
    """Idle seconds by what the host was doing: each gap goes, piece by
    piece, to the innermost (shortest) host span that covers the piece, or
    to ``unattributed``. Returns [[name, seconds], ...], longest first."""
    totals = {}
    spans = sorted(host_spans, key=lambda s: s[2])      # shortest first
    for lo, hi in gaps:
        rest = [(lo, hi)]
        for name, start, dur in spans:
            end, left = start + dur, []
            for a, b in rest:
                s, e = max(a, start), min(b, end)
                if e > s:
                    totals[name] = totals.get(name, 0.0) + (e - s)
                    if a < s:
                        left.append((a, s))
                    if e < b:
                        left.append((e, b))
                else:
                    left.append((a, b))
            rest = left
            if not rest:
                break
        for a, b in rest:
            totals["unattributed"] = totals.get("unattributed", 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


_HLO = re.compile(r"^%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """A short label for an op event. On a TPU the event's name is the
    whole HLO instruction (``%fusion.12 = f32[3072,768]{...} fusion(...)``):
    the label is its opcode and result shape without layouts, so the same
    op of every layer falls under one label."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"[.\d]+$", "", name.lstrip("%")) or name
    shape = re.sub(r"\{[^}]*\}", "", m.group(1))
    return f"{m.group(2)} {shape}"[:96]


def top_ops(events, window, top: int = 10):
    """[[label, seconds], ...] by summed duration. Ops that run for a
    quarter of the window or more in one piece are containers (a ``while``
    with its body's ops inside it) and are left out."""
    lo, hi = window
    totals = {}
    for name, _, dur, *_ in clip(events, window):
        if dur >= 0.25 * (hi - lo):
            continue
        key = op_label(name)
        totals[key] = totals.get(key, 0.0) + dur
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def window_of(host_spans, name: str):
    """The [start, end) of the one host span called ``name``."""
    for n, start, dur in host_spans:
        if n == name:
            return (start, start + dur)
    raise LookupError(f"no host span {name!r} in the trace")


class TraceSummary:
    """What the readers read from a traced run."""

    def __init__(self, devices: dict, host_spans: list, window, chips: int):
        self.devices = {k: devices[k] for k in sorted(devices)[:chips]}
        self.host_spans = host_spans
        self.window = window
        self.window_s = (window[1] - window[0]) / 1e9
        if not self.devices:
            raise LookupError("the trace holds no device op line")
        self.busy_s = sum(busy_seconds(ev, window)
                          for ev in self.devices.values()) / len(self.devices)

    def breakdown(self) -> dict:
        first = next(iter(self.devices.values()))
        return {"device_ops": top_ops(first, self.window),
                "idle_gaps": attribute_gaps(idle_gaps(first, self.window),
                                            self.host_spans)}
