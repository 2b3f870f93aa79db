"""The readers PR 27 adds — device time per step, time by named scope of
the compiled step, a scope's share of its roofline — on hand-made events
and on the cut of a recorded v5e trace, each with a hand-made scope
index; and the ten ``per_layer`` entries that name them."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import trace
from benchmarks.harness.manifest import Cell
from benchmarks.readers import (device_step_ms, histogram_mean, scope_ms,
                                scope_roofline)

ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000
CELL = "bert-base-train-s512"
EXE = "estimator_train_step"
ATTENTION = r"block_\d+/attention(/|$)"
FFN = r"block_\d+/(intermediate|output)$"
OPTIMIZER = r"^optimizer(/|$)"
NEW = ["device_step_ms.train", "attention_ms.train", "ffn_ms.train",
       "optimizer_ms.train", "attention_block_roofline.train",
       "unscoped_share.train", "mixed_scope_share.train",
       "fit_prepare_ms.train", "first_batch_ms.train",
       "epoch_flush_ms.train"]


def ev(name, start_ms, dur_ms):
    return (name, int(start_ms * MS), int(dur_ms * MS), "")


def entry(scope, phase="forward", scopes=None, opcode="fusion"):
    return {"scope": scope, "phase": phase, "opcode": opcode,
            "scopes": [scope] if scopes is None and scope else scopes or []}


# two steps of 50 ms in a 100 ms window; names as a v5e trace gives them
HAND = [
    ev("%fusion.1 = bf16[32,512,2304]{2,1,0} fusion(%p)", 0, 10),
    ev("%fusion.2 = bf16[32,12,512,512]{3,2,1,0} fusion(%fusion.1)", 5, 10),
    ev("%convolution_add_fusion.3 = bf16[32,512,3072]{2,1,0} fusion()", 20, 5),
    ev("%fusion.4 = (f32[3072,768]{1,0}, f32[3072,768]{1,0}) fusion()", 30, 10),
    ev("%multiply_add_fusion.5 = f32[768]{0} fusion()", 40, 4),
    ev("%slice-done.6 = f32[768,3,64]{0,2,1} async-done()", 44, 1),
    ev("%copy.7 = f32[2]{0} copy(%p)", 45, 1),
    ev("%while.8 = (s32[], f32[8]{0}) while(%t)", 50, 40),
    ev("%fusion.9 = bf16[32,512,768]{2,1,0} fusion()", 55, 5),
]
INDEX = {
    "fusion.1": entry("Classifier/bert/block_0/attention"),
    "fusion.2": entry("Classifier/bert/block_0/attention/bqhd,bkhd->bhqk"),
    "convolution_add_fusion.3": entry(
        "Classifier/bert/block_0/intermediate"),
    # a weight-gradient product fused with the optimizer's update
    "fusion.4": entry("Classifier/bert/block_0/output", "backward",
                      ["Classifier/bert/block_0/output", "optimizer"]),
    "multiply_add_fusion.5": entry("optimizer", "optimizer"),
    "slice-done.6": entry(None, "other", opcode="async-done"),
    "while.8": entry("Classifier/bert/Dropout_0", opcode="while"),
    "fusion.9": entry("Classifier/bert/block_1/attention/out", "backward"),
}
WINDOW = (0, 100 * MS)


def make_run(events, window, traced_units, peaks=True, telemetry=None):
    """A ``run`` as ``benchmarks/run.py`` hands it to the readers of a
    traced training run."""
    from benchmarks.harness import device
    summary = trace.TraceSummary({0: events}, [], window, 1)
    return {"attempted": 64, "failed": 0, "mode": "train",
            "evidence": {"traced_units": traced_units, "rate": 194.7,
                         "telemetry": telemetry or {"start": {}, "end": {}}},
            "trace": summary, "breakdown": summary.breakdown(),
            "peaks": device.peaks_for("TPU v5 lite") if peaks else None}


@pytest.fixture
def cell():
    return Cell(CELL)


@pytest.fixture
def hand_index(monkeypatch):
    monkeypatch.setattr(scope_ms, "load_index",
                        lambda name: INDEX if name == EXE else None)


def test_device_time_per_step_needs_only_the_trace(cell):
    run = make_run(HAND, WINDOW, traced_units=2 * 32)
    # busy: 0-15, 20-25, 30-46, 50-90 (the container covers fusion.9)
    assert run["trace"].busy_s == pytest.approx(0.076)
    assert device_step_ms.read(cell, run) == pytest.approx(38.0)
    run["evidence"]["traced_units"] = 0
    assert device_step_ms.read(cell, run) is None


def test_time_by_scope_is_a_union_over_the_matching_instructions(
        cell, hand_index):
    run = make_run(HAND, WINDOW, traced_units=2 * 32)
    read = lambda **args: scope_ms.read(cell, run, EXE, **args)  # noqa: E731
    # fusion.1 and fusion.2 overlap: 15 ms, and fusion.9's 5: over 2 steps
    assert read(pattern=ATTENTION) == pytest.approx(10.0)
    # the mixed fusion counts with its product: 5 + 10 ms
    assert read(pattern=FFN) == pytest.approx(7.5)
    assert read(pattern=OPTIMIZER) == pytest.approx(2.0)
    assert read(pattern=r"no_such_scope") == 0.0
    # of the 41 ms in which an op other than the container ran: the
    # nameless async-done and the copy the index does not know, 2 ms;
    # the fusion under two of the three groups, 10 ms
    assert read(unscoped=True, share=True) == pytest.approx(100 * 2 / 41)
    assert read(mixed=[ATTENTION, FFN, OPTIMIZER], share=True) \
        == pytest.approx(100 * 10 / 41)
    groups = read(pattern=ATTENTION) + read(pattern=FFN) \
        + read(pattern=OPTIMIZER)
    assert groups <= device_step_ms.read(cell, run)


def test_roofline_share_is_the_least_time_over_the_scopes_time(
        cell, hand_index, capsys):
    from benchmarks.flops import bert
    run = make_run(HAND, WINDOW, traced_units=2 * 32)
    share = scope_roofline.read(cell, run, EXE, ATTENTION,
                                "attention_block_needs")
    need = bert.attention_block_needs(cell.config, cell.traffic, 64,
                                      "train")
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert share == pytest.approx(100.0 * least / 0.020)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
    assert "bound by operations" in run["notes"][-1]
    # without peaks (a device the table does not know): nothing
    assert scope_roofline.read(
        cell, make_run(HAND, WINDOW, 64, peaks=False), EXE, ATTENTION,
        "attention_block_needs") is None
    # a scope no op ran in: nothing, not a division by nought
    assert scope_roofline.read(cell, run, EXE, "no_such_scope",
                               "attention_block_needs") is None


def test_a_program_without_a_scope_index_gives_nothing_to_read(
        cell, monkeypatch):
    """The parent commit of PR 27: the readers return None and the line
    leaves the metrics out; device_step_ms still reads."""
    from analytics_zoo_tpu.common import profiling
    run = make_run(HAND, WINDOW, traced_units=64)
    monkeypatch.setattr(profiling, "scope_index", lambda name: None)
    assert scope_ms.read(cell, run, EXE, pattern=ATTENTION) is None
    assert scope_roofline.read(cell, run, EXE, ATTENTION,
                               "attention_block_needs") is None
    monkeypatch.delattr(profiling, "scope_index")
    assert scope_ms.load_index(EXE) is None
    assert scope_ms.read(cell, run, EXE, unscoped=True, share=True) is None
    assert device_step_ms.read(cell, run) == pytest.approx(38.0)


def test_the_programs_own_index_is_what_load_index_returns():
    from analytics_zoo_tpu.common import profiling, telemetry
    telemetry.reset_for_tests()
    assert scope_ms.load_index("estimator_train_step") is None

    class Exe:
        def as_text(self):
            return ("ENTRY %main (a: f32[2]) -> f32[2] {\n"
                    "  %a = f32[2]{0} parameter(0)\n"
                    "  ROOT %neg.1 = f32[2]{0} negate(f32[2]{0} %a), "
                    'metadata={op_name="jit(step_fn)/optimizer/neg"}\n}\n')

        def cost_analysis(self):
            return {"flops": 2.0}

    assert profiling.note_executable("estimator_train_step", Exe()) == 2.0
    assert scope_ms.load_index("estimator_train_step") == {
        "neg.1": {"scope": "optimizer", "phase": "optimizer",
                  "scopes": ["optimizer"], "opcode": "negate"}}
    telemetry.reset_for_tests()


# ------------------------------------------------- the recorded v5e cut

FIXTURE = json.loads((ROOT / "benchmarks" / "fixtures"
                      / "v5e_train_trace_cut.json").read_text())


def _recorded_run(peaks=True):
    """The cut as one traced stretch of one step of batch 32, and an index
    made by hand for it: the weights' converts to bf16 under the attention
    block they feed, the copies under the optimizer, the sort nowhere."""
    import re
    events = [(n, s, d, "") for n, s, d in FIXTURE["ops"]]
    index = {}
    for name, _, _, _ in events:
        inst = re.match(r"^%?([\w.\-]+)", name).group(1)
        if inst.startswith("convert."):
            index[inst] = entry("Classifier/bert/block_0/attention/query")
        elif inst.startswith("copy."):
            index[inst] = entry("optimizer", "optimizer", opcode="copy")
        elif inst.startswith("slice-done"):
            index[inst] = entry(None, "other", opcode="async-done")
        elif inst.startswith("pad_maximum_fusion"):
            index[inst] = entry(
                "Classifier/bert/block_0/intermediate", "forward",
                ["Classifier/bert/block_0/intermediate",
                 "Classifier/bert/block_0/attention"])
    lo = events[0][1]
    hi = max(s + d for _, s, d, _ in events)
    return make_run(events, (lo, hi), traced_units=32, peaks=peaks), index


def test_the_readers_on_the_recorded_cut_each_return_a_number(
        cell, monkeypatch):
    run, index = _recorded_run()
    monkeypatch.setattr(scope_ms, "load_index", lambda name: index)
    values = {}
    for name in NEW[:7]:
        spec = cell.metric_file(name)
        reader = {"device_step_ms": device_step_ms, "scope_ms": scope_ms,
                  "scope_roofline": scope_roofline}[spec["reader"]]
        values[name] = reader.read(cell, run, **spec.get("args", {}))
        assert isinstance(values[name], float), name
    busy_ms = 1e3 * run["trace"].busy_s
    assert values["device_step_ms.train"] == pytest.approx(busy_ms)
    assert 0 < values["attention_ms.train"] < busy_ms
    assert 0 < values["ffn_ms.train"] < busy_ms
    assert 0 < values["optimizer_ms.train"] < busy_ms
    assert values["attention_ms.train"] + values["ffn_ms.train"] \
        + values["optimizer_ms.train"] <= busy_ms
    # the slice-dones are nameless and the sort unknown: a real share
    assert 20 < values["unscoped_share.train"] < 100
    assert 0 < values["mixed_scope_share.train"] < 100
    assert values["attention_block_roofline.train"] > 0
    # the same sum by another road: per-instruction durations
    import re
    by_hand = sum(d for n, _, d in FIXTURE["ops"]
                  if re.match(r"^%convert\.", n))
    assert values["attention_ms.train"] * 1e6 <= by_hand + 1
    run_np, _ = _recorded_run(peaks=False)
    spec = cell.metric_file("attention_block_roofline.train")
    assert scope_roofline.read(cell, run_np, **spec["args"]) is None


def test_phase_means_come_from_the_three_new_labels(cell):
    def hist(count, total):
        return {"count": count, "sum": total}
    snaps = {"start": {"zoo_train_phase_seconds": {
                 "phase=prepare": hist(4, 0.4),
                 "phase=first_batch": hist(4, 0.04),
                 "phase=flush": hist(4, 0.2),
                 "phase=data_wait": hist(64, 0.15)}},
             "end": {"zoo_train_phase_seconds": {
                 "phase=prepare": hist(8, 0.44),
                 "phase=first_batch": hist(8, 0.12),
                 "phase=flush": hist(8, 0.36),
                 "phase=data_wait": hist(128, 0.30)}}}
    run = make_run(HAND, WINDOW, 64, telemetry=snaps)
    got = {name: histogram_mean.read(
               cell, run, **cell.metric_file(name)["args"])
           for name in NEW[7:]}
    assert got == {"fit_prepare_ms.train": pytest.approx(10.0),
                   "first_batch_ms.train": pytest.approx(20.0),
                   "epoch_flush_ms.train": pytest.approx(40.0)}
    # a parent that keeps no such label: nothing, and no error
    for which in snaps.values():
        for label in ("phase=prepare", "phase=first_batch", "phase=flush"):
            del which["zoo_train_phase_seconds"][label]
    for name in NEW[7:]:
        assert histogram_mean.read(
            cell, run, **cell.metric_file(name)["args"]) is None


# ------------------------------------------------------- the new entries

@pytest.mark.parametrize("metric", NEW)
def test_new_entry_is_last_and_names_a_layer_and_both_cells(metric):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(NEW):] == NEW
    m = manifest["per_layer"][names.index(metric)]
    assert m["moves"] == "train_samples_per_s"
    assert m["workloads"] == ["bert-base-train-s512",
                              "bert-base-train-s128"]
    assert m["layer"] == ("trainer" if m["source"] == "program_span"
                          else "kernels")
    assert m["source"] in ("device_trace", "program_span")
    spec = json.loads((ROOT / "benchmarks" / "metrics"
                       / f"{metric}.json").read_text())
    assert set(spec) <= {"reader", "args"}
    if spec["reader"] != "histogram_mean" and "args" in spec:
        assert spec["args"]["executable"] == EXE


def test_the_cells_report_the_new_metrics_beside_the_old(cell):
    reported = [m["name"] for m in cell.per_layer]
    assert reported[-len(NEW):] == NEW
    assert {"window_compiles.train", "data_wait_ms.train",
            "host_dispatch_ms.train", "step_mfu.train",
            "device_idle.train"} <= set(reported)
