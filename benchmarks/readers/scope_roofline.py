"""A named scope's share of its roofline: the least time the chip could
take for what the mathematics of the scope needs — the larger of its
operations over the peak FLOP/s and its least bytes over the peak B/s
(``benchmarks/peaks.json``), for the rows of the traced stretch on one
chip, by the function ``needs`` of ``benchmarks/flops/<family>.py`` — over
the seconds in which an op of the scope ran (``scope_ms``: a fusion counts
with the matrix product inside it, so the seconds can only be counted too
high and the share too low). In percent; a note says which of the two
bounds it. Nothing without peaks, a scope index or a matching op."""

from benchmarks.readers import scope_ms


def read(cell, run, executable: str, pattern: str, needs: str):
    peaks = run.get("peaks")
    index = scope_ms.load_index(executable)
    rows = run["evidence"].get("traced_units")
    if not peaks or not index or not rows:
        return None
    seconds = scope_ms.covered_seconds(
        scope_ms.select(scope_ms.op_events(run), index, pattern))
    if seconds <= 0:
        return None
    need = getattr(cell.module("flops"), needs)(
        cell.config, cell.traffic, rows / cell.chips, run["mode"])
    by_flops = need["flops"] / peaks["flops_per_s"]
    by_bytes = need["bytes"] / peaks["bytes_per_s"]
    run.setdefault("notes", []).append(
        f"{needs}: bound by "
        f"{'operations' if by_flops >= by_bytes else 'bytes'} "
        f"({by_flops:.4f} s by operations, {by_bytes:.4f} s by bytes) "
        f"against {seconds:.4f} s in which an op of {pattern!r} ran")
    return 100.0 * max(by_flops, by_bytes) / seconds
