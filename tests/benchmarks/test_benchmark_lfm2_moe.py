"""The family ``lfm2_moe`` as the benchmark holds it: the configuration
file against the published row and its stated cut, the operation counts
against a count by hand, and the two readers of the program's counters on
hand-made runs.

The family's files lie under ``benchmarks/`` and its entries at the end of
``BENCHMARK.json``'s lists: the manifest, rehearsal, faults and scopes
tests take the cell in by its name, so what they ask of every cell is not
asked again here. (The last line
of ``test_benchmark_new_family.py``'s first test pins the manifest's
families to ``{"bert", "toy"}`` and fails with any second family in
``BENCHMARK.json``; no ``model_config`` PR may edit it: PERF.md,
section 7.)"""

import json

import numpy as np
import pytest

from benchmarks.flops import lfm2_moe as flops
from benchmarks.harness import tiny
from benchmarks.harness.manifest import ROOT, Cell
from benchmarks.models import lfm2_moe as model_lib
from benchmarks.readers import counter_share, gauge_max
from benchmarks.references import lfm2_moe as ref

CELL = "lfm2-8b-a1b-train-b2-s8192"
CONFIG = "lfm2-8b-a1b"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["moe_ms.train", "conv_ms.train", "lm_head_ms.train",
       "moe_experts_roofline.train", "moe_local_share.train",
       "moe_load_imbalance.train", "mlp_ms.train", "loss_ms.train",
       "moe_window_padding.train"]
JOINED = ["attention_ms.train", "attention_block_roofline.train",
          "mixed_scope_share.train"]
CFG = json.loads((ROOT / "benchmarks" / "configs"
                  / "lfm2-8b-a1b.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmarks" / "traffic"
                      / "epochs-b2-s8192.json").read_text())
#: the published config.json's numbers (the catalog row of the
#: model-configs guide), widths first
PUBLISHED = dict(
    hidden_size=2048, intermediate_size=7168, moe_intermediate_size=1792,
    num_attention_heads=32, num_key_value_heads=8, num_experts_per_tok=4,
    conv_L_cache=3, rope_theta=1000000, norm_eps=1e-05,
    routed_scaling_factor=1, max_position_embeddings=128000,
    norm_topk_prob=True, use_expert_bias=True, conv_bias=False,
    model_type="lfm2_moe")
CUT = dict(num_hidden_layers=(24, 5), num_dense_layers=(2, 1),
           num_experts=(32, 8), vocab_size=(65536, 16384))


def test_every_published_width_is_unchanged_and_every_cut_is_stated():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
        assert key not in CFG["reduced"]
    for key, (published, held) in CUT.items():
        assert CFG["published"][key] == published and CFG[key] == held
        assert key in CFG["reduced"] and key in CFG["cut"]
    assert set(CFG["reduced"]) == set(CUT) | {"layer_types"}
    # one whole period in its published order behind the dense layer
    full = CFG["published"]["layer_types"]
    assert len(full) == 24 and CFG["layer_types"] == [full[0]] + full[2:6]
    assert CFG["layer_types"][1:] == ["full_attention", "conv", "conv",
                                      "conv"]
    # the router keeps its published width; the held are ids 0-7
    assert CFG["router_experts"] == CFG["published"]["num_experts"] == 32
    assert CFG["held_experts"] == list(range(8))
    for key in ("router_scores", "qk_norm", "conv_gates", "tied_embeddings",
                "initializer_range", "expert_bias"):
        assert key in CFG["assumed"]
    assert "4 chips" in CFG["deployment"]
    assert CFG["calibration_batch"] == TRAFFIC["batch_size"]
    assert CFG["calibration_seq_len"] == TRAFFIC["seq_len"]


def test_the_traffic_hands_the_program_and_the_reference_one_adam():
    from analytics_zoo_tpu.learn.optimizers import Adam, Optimizer
    opt, args = TRAFFIC["optimizer"], TRAFFIC["optimizer_args"]
    built = Optimizer.get(opt)
    assert isinstance(built, Adam)
    assert (built.lr, built.b1, built.b2, built.eps) \
        == (args["lr"], args["b1"], args["b2"], args["eps"]) \
        == (1e-5, 0.9, 0.999, 1e-8)
    assert TRAFFIC["batch_size"] * TRAFFIC["seq_len"] == 16384


def test_parameters_held_are_the_cut_models():
    shapes = ref.param_shapes(CFG)
    import jax
    count = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple)))
    # embedding 33.6M (tied); layer 0 16.8M + 44.0M; three sparse conv
    # layers of 104.9M; the sparse attention layer 98.6M
    assert count == 507_820_288
    assert count * 16 < 0.55 * 16e9


def test_inputs_are_ids_of_the_slice_and_each_positions_next_id():
    x, y = model_lib.make_inputs(CFG, TRAFFIC, np.random.default_rng(5), 3)
    assert x.shape == y.shape == (3, 8192) and x.dtype == np.int32
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    assert 0 <= x.min() and max(x.max(), y.max()) < CFG["vocab_size"]
    assert len({row.tobytes() for row in x}) == 3


def test_operation_counts_are_the_cut_models_mathematics():
    """By hand, per token forward: a conv layer 2*2048*6144 + 2*2048*2048
    (+ 6*2048 for the taps); the dense block 6*2048*7168; a sparse layer's
    router 2*2048*32 and ONE expert of 6*2048*1792 (4 of 32 picked, 8
    held); the head 2*2048*16384; attention's projections 2*(2*2048*2048
    + 2*2048*512) and the causal half of its scores, 2*L*2048 a token."""
    L = 8192
    conv = 2 * 2048 * 6144 + 2 * 2048 * 2048 + 6 * 2048
    per_token = (4 * conv + 6 * 2048 * 7168
                 + 4 * (2 * 2048 * 32 + 6 * 2048 * 1792)
                 + 2 * 2048 * 16384
                 + 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * L * 2048)
    assert flops.expert_rows_per_token(CFG) == 1.0
    assert flops.sample_flops(CFG, TRAFFIC, "train") \
        == pytest.approx(3 * L * per_token, rel=1e-12)
    step = 2 * flops.sample_flops(CFG, TRAFFIC, "train")
    assert 21.0e12 < step < 21.6e12
    need = flops.moe_experts_needs(CFG, TRAFFIC, rows=2, mode="train")
    assert need["flops"] == 3 * 4 * 16384 * 6 * 2048 * 1792
    # a pass reads 8 experts' three matrices once a step, in 16 bits, and
    # reads and writes each routed row once
    assert need["bytes"] == 3 * 4 * (8 * 3 * 2048 * 1792 * 2
                                     + 2 * 16384 * 2048 * 2)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
    att = flops.attention_block_needs(CFG, TRAFFIC, rows=2, mode="train")
    assert att["flops"] == 3 * 2 * (
        2 * L * (2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * L * L * 2048)
    assert att["bytes"] == 3 * 2 * 2 * L * 2048 * 2
    # what is counted is a part of the step, never more
    assert need["flops"] + att["flops"] < step


def test_counts_do_not_follow_what_an_implementation_executes():
    """A wider first window, a second window, rematerialisation: none is a
    key of the configuration or the traffic, so none can move a count."""
    import inspect
    source = inspect.getsource(flops)
    for word in ("slack", "remat", "window", "analytics_zoo_tpu"):
        assert word not in source.split('"""', 2)[2]


def _run(start, end):
    return {"evidence": {"telemetry": {"start": start, "end": end}}}


@pytest.fixture
def cell():
    """The cell with its real files under the real manifest."""
    return Cell(CELL)


def test_local_share_is_the_held_part_of_the_windows_assignments(cell):
    spec = cell.metric_file("moe_local_share.train")
    assert spec["reader"] == "counter_share"
    family = "zoo_moe_assignments_total"
    start = {family: {"held=true,layer=block_1/moe": 100.0,
                      "held=false,layer=block_1/moe": 300.0}}
    end = {family: {"held=true,layer=block_1/moe": 100.0 + 24.0,
                    "held=false,layer=block_1/moe": 300.0 + 76.0,
                    "held=true,layer=block_2/moe": 26.0,
                    "held=false,layer=block_2/moe": 74.0}}
    assert counter_share.read(cell, _run(start, end), **spec["args"]) \
        == pytest.approx(25.0)
    # a program without the counter (the parent): nothing to read
    assert counter_share.read(cell, _run({}, {}), **spec["args"]) is None
    assert counter_share.read(cell, _run(end, end), **spec["args"]) is None


def test_load_imbalance_is_the_worst_layers_gauge_at_the_windows_end(cell):
    spec = cell.metric_file("moe_load_imbalance.train")
    assert spec["reader"] == "gauge_max"
    end = {"zoo_moe_load_imbalance": {"layer=block_1/moe": 1.04,
                                      "layer=block_3/moe": 1.11}}
    assert gauge_max.read(cell, _run({}, end), **spec["args"]) == 1.11
    assert gauge_max.read(cell, _run({}, {}), **spec["args"]) is None


def test_window_padding_is_the_unused_part_of_the_first_windows_rows(cell):
    spec = cell.metric_file("moe_window_padding.train")
    assert spec["reader"] == "counter_share"
    family = "zoo_moe_window_rows_total"
    start = {family: {"layer=block_1/moe,used=true": 16.0,
                      "layer=block_1/moe,used=false": 4.0}}
    end = {family: {"layer=block_1/moe,used=true": 16.0 + 166.0,
                    "layer=block_1/moe,used=false": 4.0 + 38.8,
                    "layer=block_2/moe,used=true": 166.0,
                    "layer=block_2/moe,used=false": 38.8}}
    assert counter_share.read(cell, _run(start, end), **spec["args"]) \
        == pytest.approx(100 * 38.8 / 204.8)
    assert counter_share.read(cell, _run({}, {}), **spec["args"]) is None


def test_the_cell_reports_its_nine_and_joins_three_lists(cell):
    reported = [m["name"] for m in cell.per_layer]
    assert reported[-len(NEW):] == NEW
    assert set(JOINED) | {"step_mfu.train", "unscoped_share.train"} \
        <= set(reported)
    assert not {"ffn_ms.train", "dropout_ms.train"} & set(reported)
    assert [m["name"] for m in cell.end_to_end] == ["train_samples_per_s",
                                                    "setup_s"]
    assert set(cell.limits) == {"grad1", "grad1_diff", "dparam1",
                                "dparam_median"}
    assert cell.traffic["control"] == "fp8_e4m3" and cell.chips == 1


# ------------------- what tests/benchmarks/ asks of a family's entries

def test_the_entries_are_appended_and_joined_at_the_end_of_their_lists():
    assert MANIFEST["configs"][-1]["name"] == CONFIG
    assert MANIFEST["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "epochs-b2-s8192",
        "chips": 1, "why": MANIFEST["workloads"][-1]["why"]}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(NEW):] == NEW and len(names) == len(set(names))
    assert [m["name"] for m in MANIFEST["end_to_end"]] \
        == ["train_samples_per_s", "setup_s"]
    for key in ("per_layer", "end_to_end"):
        for m in MANIFEST[key]:
            if m["name"] in JOINED + ["train_samples_per_s"]:
                assert m["workloads"][-1] == CELL
                assert m["workloads"].count(CELL) == 1
            elif m["name"] not in NEW:
                assert CELL not in m.get("workloads", [])
    assert CELL in tiny.all_cells("train_epochs")
    assert MANIFEST["configs"][-1]["source"] == CFG["source"]
    assert MANIFEST["configs"][-1]["reduced"] == CFG["reduced"]
    assert "drifts" in MANIFEST["workloads"][-1]["why"]  # not stationary
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    for m in MANIFEST["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["layer"] in layers
        assert m["moves"] == "train_samples_per_s"
        assert "_roofline" not in m["name"] or m["unit"] == "%"
