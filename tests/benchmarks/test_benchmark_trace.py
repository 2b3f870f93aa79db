"""The reduction from a trace to busy time, idle gaps and per-scope time,
on hand-made intervals and on a cut of a recorded v5e trace; the counts
of needed operations against hand-worked numbers."""

import json
import sys
from pathlib import Path

import pytest

from benchmarks.harness import trace

ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000


def ev(name, start_ms, dur_ms, scope=""):
    return (name, start_ms * MS, dur_ms * MS, scope)


HAND = [ev("fusion.1", 0, 10, "jit(step)/bert/block_0/attention/query"),
        ev("fusion.2", 5, 10, "jit(step)/bert/block_0/attention/out"),
        ev("fusion.3", 20, 5, "jit(step)/bert/block_0/intermediate"),
        ev("copy.4", 40, 10, "jit(step)/bert/block_1/attention/out"),
        ev("while.5", 60, 30, ""),
        ev("fusion.6", 65, 5, "jit(step)/bert/block_1/output")]
WINDOW = (0, 100 * MS)


def test_busy_is_the_union_of_intervals():
    assert trace.union([(0, 10), (5, 15), (20, 25)]) == [[0, 15], [20, 25]]
    assert trace.busy_seconds(HAND, WINDOW) == pytest.approx(0.060)
    # clipped to the window
    assert trace.busy_seconds(HAND, (10 * MS, 50 * MS)) \
        == pytest.approx(0.005 + 0.005 + 0.010)


def test_idle_gaps_and_their_attribution():
    gaps = trace.idle_gaps(HAND, WINDOW)
    assert gaps == [(15 * MS, 20 * MS), (25 * MS, 40 * MS),
                    (50 * MS, 60 * MS), (90 * MS, 100 * MS)]
    host = [("fit_epoch", 0, 55 * MS), ("data_wait", 26 * MS, 10 * MS)]
    named = dict(trace.attribute_gaps(gaps, host))
    assert named["data_wait"] == pytest.approx(0.010)
    assert named["fit_epoch"] == pytest.approx(0.005 + 0.005 + 0.005)
    assert named["unattributed"] == pytest.approx(0.005 + 0.010)
    assert sum(named.values()) == pytest.approx(0.040)


def test_scope_time_counts_nested_and_overlapping_ops_once():
    assert trace.scope_seconds(HAND, WINDOW, "/attention/") \
        == pytest.approx(0.015 + 0.010)
    assert trace.scope_seconds(HAND, WINDOW, "/no_such_scope/") == 0.0


def test_top_ops_leave_out_containers_and_strip_numbering():
    ops = dict(trace.top_ops(HAND, WINDOW))
    assert "while" not in ops
    assert ops["fusion"] == pytest.approx(0.010 + 0.010 + 0.005 + 0.005)
    assert ops["copy"] == pytest.approx(0.010)


def test_summary_averages_over_the_chips_used():
    s = trace.TraceSummary({0: HAND, 1: HAND[:1], 7: HAND}, [], WINDOW, 2)
    assert s.busy_s == pytest.approx((0.060 + 0.010) / 2)
    assert s.window_s == pytest.approx(0.1)
    with pytest.raises(LookupError):
        trace.TraceSummary({}, [], WINDOW, 1)


def test_flops_count_the_mathematics_and_do_not_import_the_program():
    cfg = json.loads((ROOT / "benchmarks" / "configs"
                      / "bert-base.json").read_text())
    before = set(sys.modules)
    from benchmarks.flops import bert
    assert not any(m.startswith("analytics_zoo_tpu")
                   for m in set(sys.modules) - before)
    # 12 x (4 x 768^2 + 2 x 768 x 3072) = 84,934,656: the "85M"
    assert bert.encoder_matmul_params(cfg) == 84_934_656
    fwd = bert.forward_flops(cfg, 512)
    by_hand = (2 * 84_934_656 * 512 + 12 * 4 * 512 * 512 * 768
               + 2 * 768 * 768 + 2 * 768 * 2)
    assert fwd == by_hand
    assert 96e9 < fwd < 98e9            # ISSUE 26: about 97 GFLOP a record
    t = {"seq_len": 512}
    assert bert.sample_flops(cfg, t, "train") == 3 * fwd
    assert bert.sample_flops(cfg, t, "serve") == fwd
    need = bert.attention_block_needs(cfg, t, 32, "train")
    per_layer_row = 2 * 512 * 4 * 768 * 768 + 4 * 512 * 512 * 768
    assert need["flops"] == 3 * 12 * 32 * per_layer_row
    assert need["bytes"] == 3 * 12 * 2 * 32 * 512 * 768 * 2


def test_a_share_of_peak_cannot_pass_100_percent_by_its_counts():
    """At the peak rate itself the needed-work share reads exactly 100."""
    from benchmarks.flops import bert
    from benchmarks.harness import device
    cfg = json.loads((ROOT / "benchmarks" / "configs"
                      / "bert-base.json").read_text())
    peaks = device.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["bytes_per_s"] == 819e9
    flops = bert.sample_flops(cfg, {"seq_len": 512}, "train")
    rate_at_peak = peaks["flops_per_s"] / flops
    assert 100.0 * flops * rate_at_peak / peaks["flops_per_s"] \
        == pytest.approx(100.0)
    with pytest.raises(KeyError):
        device.peaks_for("TPU v9 imaginary")


# ------------------------------------------------- the recorded v5e cut

FIXTURE = json.loads((ROOT / "benchmarks" / "fixtures"
                      / "v5e_train_trace_cut.json").read_text())


def _recorded():
    return [(n, s, d, "") for n, s, d in FIXTURE["ops"]]


def _sweep_busy_ns(events, lo, hi):
    """Busy time by a sweep over interval end points: written differently
    from ``trace.union`` on purpose."""
    points = []
    for _, s, d, *_ in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for at, step in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += at - last
        depth += step
        last = at
    return busy


def test_recorded_trace_layout_is_what_the_loader_expects():
    assert "/device:TPU:0" in FIXTURE["planes"]
    assert trace.DEVICE_PLANE.match("/device:TPU:0")
    assert trace.OP_LINE in FIXTURE["device_lines"]
    # the op line carries timing only: no name stack to find a scope by
    assert FIXTURE["op_stats_keys"] == [
        "Time Scale Multiplier", "device_duration_ps", "device_offset_ps"]
    assert any(n.startswith(trace.HOST_PREFIX)
               for n, _, _ in FIXTURE["host_spans"])


def test_recorded_trace_busy_gaps_and_labels():
    events = _recorded()
    lo = events[0][1]
    hi = max(s + d for _, s, d, _ in events)
    busy = trace.busy_seconds(events, (lo, hi))
    assert busy * 1e9 == pytest.approx(_sweep_busy_ns(events, lo, hi))
    gaps = trace.idle_gaps(events, (lo, hi))
    idle = sum(b - a for a, b in gaps)
    assert idle + busy * 1e9 == pytest.approx(hi - lo)
    assert 0 < busy * 1e9 <= hi - lo
    # half the window: clipping keeps the arithmetic closed
    mid = (lo + hi) // 2
    assert trace.busy_seconds(events, (lo, mid)) * 1e9 \
        == pytest.approx(_sweep_busy_ns(events, lo, mid))
    labels = dict(trace.top_ops(events, (lo, hi), top=50))
    assert labels and all(len(k) <= 96 and "{" not in k for k in labels)
    assert sum(labels.values()) >= busy      # summed, overlaps count twice


def test_a_scope_that_the_trace_does_not_carry_reads_nothing():
    events = _recorded()
    window = (events[0][1], events[-1][1] + events[-1][2])
    assert trace.scope_seconds(events, window, "/attention/") == 0.0


def test_module_events_give_the_device_step_time():
    durs = [d for n, _, d in FIXTURE["modules"] if n.startswith("jit_step_fn")]
    assert durs and all(0.15e9 < d < 0.17e9 for d in durs)   # 161 ms a step
