"""The family ``sdar_moe`` as the benchmark holds it: the configuration
file against the published row and its stated cut, the reference's
parameter layout against ``module.init``, the operation counts against a
count over the dense mask, the router's calibration at the tiny size, the
scopes the metric files name in the compiled step, the two readers of the
program's counter on hand-made runs, and a wrong mask planted under the
timed path.

The family's files lie under ``benchmarks/`` and its entries at the end of
``BENCHMARK.json``'s lists: the manifest, rehearsal, faults and scopes
tests take the cell in by its name, so what they ask of every cell is not
asked again here. (The last line of ``test_benchmark_new_family.py``'s
first test pins the manifest's families to ``{"bert", "toy"}`` and fails
with any further family in ``BENCHMARK.json``; no ``model_config`` PR may
edit it: PERF.md, section 7.)"""

import json
import re

import numpy as np
import pytest

from benchmarks.flops import sdar_moe as flops
from benchmarks.harness import tiny
from benchmarks.harness.manifest import ROOT, Cell
from benchmarks.models import sdar_moe as model_lib
from benchmarks.readers import counter_share
from benchmarks.references import sdar_moe as ref

CELL = "sdar-30b-a3b-train-b1-s8192"
CONFIG = "sdar-30b-a3b"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["diffusion_masked_share.train", "corrupt_ms.train"]
JOINED = ["attention_ms.train", "attention_block_roofline.train",
          "mixed_scope_share.train", "moe_ms.train",
          "moe_experts_roofline.train", "moe_local_share.train",
          "moe_load_imbalance.train", "moe_window_padding.train",
          "lm_head_ms.train", "loss_ms.train"]
CFG = json.loads((ROOT / "benchmarks" / "configs"
                  / "sdar-30b-a3b.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmarks" / "traffic"
                      / "epochs-b1-s8192.json").read_text())
#: the published config.json's numbers and flags (the catalog row of the
#: model-configs guide), widths first
PUBLISHED = dict(
    hidden_size=2048, head_dim=128, moe_intermediate_size=768,
    intermediate_size=6144, num_attention_heads=32, num_key_value_heads=4,
    num_experts_per_tok=8, rope_theta=1000000, rms_norm_eps=1e-06,
    max_position_embeddings=32768, max_window_layers=48,
    decoder_sparse_step=1, mlp_only_layers=[], norm_topk_prob=True,
    attention_bias=False, tie_word_embeddings=False,
    use_sliding_window=False, sliding_window=None, rope_scaling=None,
    hidden_act="silu", model_type="sdar_moe")
CUT = dict(num_hidden_layers=(48, 5), num_experts=(128, 16),
           vocab_size=(151936, 18992))


def tiny_cfg(**over) -> dict:
    return {**CFG, **model_lib.TINY, "compute_dtype": "float32", **over}


def test_every_published_width_is_unchanged_and_every_cut_is_stated():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
        assert key not in CFG["reduced"]
    for key, (published, held) in CUT.items():
        assert CFG["published"][key] == published and CFG[key] == held
        assert key in CFG["reduced"] and key in CFG["cut"]
    assert set(CFG["reduced"]) == set(CUT)
    # the floors: four layers, 8 experts, an eighth of the vocabulary
    assert CFG["num_hidden_layers"] >= 4 and CFG["num_experts"] >= 8
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    # the router keeps its published width; the held are ids 0-15
    assert CFG["router_experts"] == CFG["published"]["num_experts"] == 128
    assert CFG["held_experts"] == list(range(16))
    for key in ("block_length", "noise_schedule", "mask_token_id",
                "auxiliary_loss", "router_calibration", "initializer_range"):
        assert key in CFG["assumed"]
    assert CFG["block_length"] == 4 and CFG["noise_eps"] == 1e-3
    assert CFG["mask_token_id"] == CFG["vocab_size"] - 1
    assert "8 chips" in CFG["deployment"]
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
    # 16,384 rows through the stack, 2,048 blocks of 4
    assert 2 * TRAFFIC["batch_size"] * TRAFFIC["seq_len"] == 16384
    assert TRAFFIC["seq_len"] // CFG["block_length"] == 2048
    assert TRAFFIC["check_steps"] == 3 and TRAFFIC["control"] == "fp8_e4m3"


def test_parameters_held_are_the_cut_models():
    import jax
    count = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        ref.param_shapes(CFG), is_leaf=lambda s: isinstance(s, tuple)))
    # a layer: q and o 8.39M each, k and v 2.10M, router 0.26M, norms,
    # 16 experts of 4.72M: 94.64M; embedding and untied head 38.9M each
    layer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 \
        + 2 * 2048 + 2 * 128 + 16 * 3 * 2048 * 768
    assert layer == 94_638_336
    assert count == 5 * layer + 2 * 18992 * 2048 + 2048 == 550_984_960
    assert 8.8e9 < count * 16 < 0.56 * 16e9


def test_reference_layout_is_the_modules():
    import jax
    for cfg in (tiny_cfg(), tiny_cfg(num_hidden_layers=1, head_dim=8)):
        x, _ = model_lib.make_inputs(cfg, {"seq_len": 16},
                                     np.random.default_rng(0), 2)
        variables = jax.eval_shape(lambda: model_lib.build_module(cfg).init(
            jax.random.PRNGKey(0), x))

        def flat(tree):
            return {"/".join(str(k.key) for k in path): tuple(
                        getattr(leaf, "shape", leaf))
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        tree, is_leaf=lambda s: isinstance(s, tuple))[0]}

        assert flat(variables["params"]) == flat(ref.param_shapes(cfg))
    with pytest.raises(ValueError, match="not run"):
        model_lib.build_module(tiny_cfg(tie_word_embeddings=True))


def test_inputs_are_ids_below_the_mask_id_and_their_own_labels():
    x, y = model_lib.make_inputs(CFG, TRAFFIC, np.random.default_rng(5), 3)
    assert x.shape == y.shape == (3, 8192) and x.dtype == np.int32
    np.testing.assert_array_equal(x, y)
    assert 0 <= x.min() and x.max() < CFG["mask_token_id"]
    assert len({row.tobytes() for row in x}) == 3


@pytest.mark.parametrize("L,B", [(16, 4), (24, 2), (32, 8), (12, 1)])
def test_allowed_pairs_are_a_count_over_the_dense_mask(L, B):
    """The operations of attention are those of the ALLOWED pairs: the
    count by formula equals the count over the reference's dense mask and
    over the program's own, and ``L * (L + B)``."""
    from analytics_zoo_tpu.ops.flash_attention import BlockDiffusionMask
    cfg = dict(CFG, block_length=B)
    dense = np.asarray(ref.allowed_pairs(np.arange(2 * L), L, B))
    assert flops.allowed_pairs(cfg, L) == int(dense.sum()) == L * (L + B)
    assert int(np.asarray(BlockDiffusionMask(L, B).dense(2 * L, 2 * L))
               .sum()) == L * (L + B)
    # the leak the faults test plants allows more
    assert int(np.asarray(ref.allowed_pairs(
        np.arange(2 * L), L, B, "own_clean_block")).sum()) \
        == L * (L + B) + L * B


def test_operation_counts_are_the_cut_models_mathematics():
    """By hand, forward, one sequence of L = 8,192 ids: 2L rows through
    each of 5 layers — projections 2 * (2*2048*4096 + 2*2048*512) a row,
    the router 2*2048*128 and ONE expert of 6*2048*768 a row (8 of 128
    picked, 16 held) — scores and weighted values 4 * 4096 an allowed
    pair, L * (L + 4) pairs; the head 2*2048*18992 over L rows."""
    L = 8192
    pairs = L * (L + 4)
    per_row = 2 * (2 * 2048 * 4096 + 2 * 2048 * 512) + 2 * 2048 * 128 \
        + 6 * 2048 * 768
    forward = 5 * (2 * L * per_row + 4 * 4096 * pairs) \
        + L * 2 * 2048 * 18992
    assert flops.expert_rows_per_token(CFG) == 1.0
    assert flops.allowed_pairs(CFG, L) == pairs
    assert flops.sample_flops(CFG, TRAFFIC, "train") \
        == pytest.approx(3 * forward, rel=1e-12)
    assert 30.0e12 < 3 * forward < 30.5e12
    need = flops.moe_experts_needs(CFG, TRAFFIC, rows=1, mode="train")
    assert need["flops"] == 3 * 5 * 16384 * 6 * 2048 * 768
    assert need["bytes"] == 3 * 5 * (16 * 3 * 2048 * 768 * 2
                                     + 2 * 16384 * 2048 * 2)
    att = flops.attention_block_needs(CFG, TRAFFIC, rows=1, mode="train")
    assert att["flops"] == 3 * 5 * (
        2 * 2 * L * (2 * 2048 * 4096 + 2 * 2048 * 512) + 4 * 4096 * pairs)
    assert att["bytes"] == 3 * 5 * 2 * 2 * L * 2048 * 2
    # a dense 2L x 2L count would be four times the pairs
    assert 3.99 * pairs < (2 * L) ** 2 < 4 * pairs
    assert need["flops"] + att["flops"] \
        < flops.sample_flops(CFG, TRAFFIC, "train")


def test_counts_do_not_follow_what_an_implementation_executes():
    import inspect
    body = inspect.getsource(flops).split('"""', 2)[2]
    for word in ("slack", "remat", "window", "analytics_zoo_tpu", "tile"):
        assert word not in body


def test_the_reference_imports_nothing_of_the_system():
    import inspect
    body = inspect.getsource(ref).split('"""', 2)[2]
    for word in ("analytics_zoo_tpu", "tile_table", "pallas", "ops.moe"):
        assert word not in body


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 9])
def test_calibration_sends_the_mask_ids_rows_to_one_held_sink(seed):
    """At a small size: after ``make_params`` only the routers' kernels
    differ from the drawn weights; on a FRESH batch (other ids, other
    noise) every row that holds the mask id takes the layer's sinks, one
    of them held and the others absent; the other rows spread within the
    tolerance; so the chip's part of the assignments is near the
    deployment's ``held / all``. The drawn routers send the mask id's
    rows, whole, wherever they fall."""
    import jax
    import jax.numpy as jnp
    cfg = tiny_cfg(router_experts=16, num_experts=4, held_experts=[0, 1, 2, 3],
                   num_experts_per_tok=4, calibration_batch=8,
                   calibration_seq_len=64, router_tolerance=0.6,
                   vocab_size=2048, mask_token_id=2047, hidden_size=64)
    k, held = 4, (0, 1, 2, 3)
    drawn = jax.tree_util.tree_map(np.asarray, ref.draw_weights(cfg, seed))
    params = ref.make_params(cfg, seed)
    changed = sorted(
        "/".join(str(k.key) for k in path)
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_leaves(drawn)) if not np.array_equal(a, b))
    assert changed == [f"decoder/block_{i}/moe/router/kernel"
                       for i in range(cfg["num_hidden_layers"])]
    # the two groups of leaves drawn at a scale of their own
    leaves = {"/".join(str(k.key) for k in path): a for path, a in
              jax.tree_util.tree_flatten_with_path(drawn)[0]}
    assert np.std(leaves["decoder/embed/embedding"]) \
        == pytest.approx(cfg["embedding_range"], rel=0.05)
    assert np.std(leaves["decoder/lm_head/kernel"]) \
        == pytest.approx(0.02, rel=0.05)
    assert np.std(leaves["decoder/block_0/moe/experts/w2"]) \
        == pytest.approx(0.02 / 96 ** 0.5, rel=0.05)
    assert np.std(leaves["decoder/block_1/attention/out/kernel"]) \
        == pytest.approx(0.02 / 96 ** 0.5, rel=0.1)

    def routed(tree):
        rng = np.random.default_rng(seed % 1000 + 1)
        ids = jnp.asarray(rng.integers(0, cfg["mask_token_id"], (8, 64),
                                       dtype=np.int32))
        xt, m, _ = ref.corrupt(ids, jax.random.PRNGKey(77), cfg)
        masked = np.concatenate([np.asarray(m), np.zeros_like(m)], 1) \
            .reshape(-1)
        p = tree["decoder"]
        x = jnp.asarray(p["embed"]["embedding"])[
            jnp.concatenate([xt, ids], 1)]
        out = []
        for i in range(cfg["num_hidden_layers"]):
            block = p[f"block_{i}"]
            h, z = ref._attend(block, x, cfg)
            logits = z.reshape(-1, z.shape[-1]) \
                @ jnp.asarray(block["moe"]["router"]["kernel"])
            _, ids_k = jax.lax.top_k(logits, k)
            out.append((np.asarray(ids_k), masked))
            x = h + ref._moe(block["moe"], z, cfg)
        return out

    for ids_k, masked in routed(params):
        chosen = {tuple(sorted(row)) for row in ids_k[masked]}
        assert len(chosen) == 1                      # one point, one choice
        sinks = chosen.pop()
        assert sum(e in held for e in sinks) == 1
        others = np.bincount(ids_k[~masked].ravel(), minlength=16)
        assert np.abs(others / others.mean() - 1).max() < 0.6
        share = np.isin(ids_k, held).mean()
        assert share == pytest.approx(4 / 16, abs=0.04)
    for ids_k, masked in routed(drawn):
        assert len({tuple(sorted(row)) for row in ids_k[masked]}) <= 2
    assert ref.sink_experts([0.1, 0.9, 0.5, 0.3, 0.8], (0, 3), 3) \
        == (1, 3, 4)
    assert ref.sink_experts([0.1, 0.9, 0.5], (0, 1, 2), 2) == (1, 2)


def test_scopes_the_metric_files_name_are_in_the_compiled_step(orca_ctx):
    """One ``fit`` at the tiny size: every pattern a joined or new metric
    file names finds an instruction of the ahead-of-time step, forward
    and backward where it names a layer."""
    from analytics_zoo_tpu.common import profiling, telemetry
    from benchmarks.harness import program
    telemetry.reset_for_tests()
    cfg = tiny_cfg()
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(0), 16)
    est = program.build_estimator(
        model_lib.build_module(cfg), model_lib.LOSS, "adam",
        ref.make_params(cfg, 0), x[:2])
    est.fit((x, y), epochs=2, batch_size=8)
    est._precompile_thread.join(timeout=300)
    index = profiling.scope_index("estimator_train_step")
    scopes = {e["scope"] for e in index.values() if e["scope"]}
    cell = Cell(CELL)
    patterns = {}
    for name in JOINED + NEW + ["optimizer_ms.train"]:
        args = cell.metric_file(name).get("args", {})
        if "pattern" in args:
            patterns[name] = args["pattern"]
    assert set(patterns) == {
        "attention_ms.train", "attention_block_roofline.train",
        "moe_ms.train", "moe_experts_roofline.train", "lm_head_ms.train",
        "loss_ms.train", "corrupt_ms.train", "optimizer_ms.train"}
    for name, pattern in patterns.items():
        phases = {e["phase"] for e in index.values()
                  if e["scope"] and re.search(pattern, e["scope"])}
        assert phases, (name, pattern, sorted(scopes))
        if "block_" in pattern:
            assert {"forward", "backward"} <= phases, name
    # the counter the new share reads grew by every position of every step
    grown = telemetry.snapshot()["zoo_diffusion_positions_total"]
    assert sum(grown.values()) == 4 * 8 * 16
    assert set(grown) == {"layer=,masked=true", "layer=,masked=false"}
    telemetry.reset_for_tests()


def _run(start, end):
    return {"evidence": {"telemetry": {"start": start, "end": end}}}


def test_masked_share_is_the_masked_part_of_the_windows_positions():
    cell = Cell(CELL)
    spec = cell.metric_file("diffusion_masked_share.train")
    assert spec == {"reader": "counter_share", "args": {
        "metric": "zoo_diffusion_positions_total", "part": "masked=true"}}
    family = "zoo_diffusion_positions_total"
    start = {family: {"layer=,masked=true": 100.0,
                      "layer=,masked=false": 300.0}}
    end = {family: {"layer=,masked=true": 100.0 + 4100.0,
                    "layer=,masked=false": 300.0 + 4092.0}}
    assert counter_share.read(cell, _run(start, end), **spec["args"]) \
        == pytest.approx(100 * 4100 / 8192)
    # a program without the counter (the parent): nothing to read
    assert counter_share.read(cell, _run({}, {}), **spec["args"]) is None
    corrupt = cell.metric_file("corrupt_ms.train")
    assert corrupt["reader"] == "scope_ms"
    assert re.search(corrupt["args"]["pattern"],
                     "BlockDiffusionLM/corrupt/lt")
    assert not re.search(corrupt["args"]["pattern"], "a/corrupted/b")


def test_the_cell_reports_its_two_and_joins_ten_lists():
    cell = Cell(CELL)
    reported = [m["name"] for m in cell.per_layer]
    assert tiny.in_order(NEW, reported)
    assert set(JOINED) | {"step_mfu.train", "unscoped_share.train",
                          "optimizer_ms.train"} <= set(reported)
    assert not {"ffn_ms.train", "dropout_ms.train", "conv_ms.train",
                "mlp_ms.train"} & set(reported)
    assert [m["name"] for m in cell.end_to_end] == ["train_samples_per_s",
                                                    "setup_s"]
    assert set(cell.limits) == {"grad1", "grad1_diff", "dparam1",
                                "dparam_median"}
    assert cell.traffic["control"] == "fp8_e4m3" and cell.chips == 1
    assert callable(ref.fp8_e4m3)


#: what BENCHMARK.json held before this family's entries were appended
#: (the parent commit's): every one of them stands before this family's
BEFORE = {"configs": ["bert-base", "lfm2-8b-a1b"],
          "workloads": ["bert-base-train-s512", "bert-base-train-s128",
                        "bert-base-train-b32-s128",
                        "lfm2-8b-a1b-train-b2-s8192"],
          "per_layer_last": "moe_window_padding.train"}


def test_the_entries_are_appended_after_what_was_there():
    """Appended, not inserted: this family's entries stand after every
    entry the parent commit had, in their own order. (Not "last": a later
    family is appended after them. ``test_benchmark_lfm2_moe.py`` asks
    "last" of its own entries and fails since this family came: PERF.md,
    section 7.)"""
    configs = [c["name"] for c in MANIFEST["configs"]]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert configs.index(CONFIG) > max(map(configs.index, BEFORE["configs"]))
    assert cells.index(CELL) > max(map(cells.index, BEFORE["workloads"]))
    entry = MANIFEST["workloads"][cells.index(CELL)]
    assert entry == {"name": CELL, "config": CONFIG, "chips": 1,
                     "traffic": "epochs-b1-s8192", "why": entry["why"]}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == NEW
    assert first > names.index(BEFORE["per_layer_last"])
    assert [m["name"] for m in MANIFEST["end_to_end"]][:2] \
        == ["train_samples_per_s", "setup_s"]
    for key in ("per_layer", "end_to_end"):
        for m in MANIFEST[key]:
            if m["name"] in JOINED + ["train_samples_per_s"]:
                assert m["workloads"].count(CELL) == 1
                assert m["workloads"].index(CELL) \
                    > m["workloads"].index(BEFORE["workloads"][-1])
            elif m["name"] not in NEW:
                assert CELL not in m.get("workloads", [])
    assert CELL in tiny.all_cells("train_epochs")
    config = MANIFEST["configs"][configs.index(CONFIG)]
    assert config["source"] == CFG["source"]
    assert config["reduced"] == CFG["reduced"]
    assert "eighth" in entry["why"] and "drifts" in entry["why"] \
        and "5 layers" in entry["why"]
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    for m in MANIFEST["per_layer"][first:first + len(NEW)]:
        assert m["workloads"] == [CELL] and m["layer"] in layers
        assert m["moves"] == "train_samples_per_s"


# ----------------------------------------- a wrong mask under the timed path

def test_a_noisy_half_that_sees_its_own_clean_block_is_not_correct(
        tmp_path, capsys, monkeypatch):
    """The fault this family adds to the faults test's: the noisy half
    sees the clean keys of its OWN block, so every masked position reads
    its answer. ``correct`` has to come out false, by the gradient."""
    from analytics_zoo_tpu.ops import flash_attention as fa
    honest = fa.BlockDiffusionMask.excluded

    def leaky(self, q_pos, k_pos):
        first_clean = self.seq_len if self.noisy else 0
        own_clean = (q_pos < first_clean) & (k_pos >= first_clean) & (
            self._block_of(q_pos) == self._block_of(k_pos - first_clean))
        return honest(self, q_pos, k_pos) & ~own_clean

    monkeypatch.setattr(fa.BlockDiffusionMask, "excluded", leaky)
    rc, line, _ = tiny.run_cell(capsys, tiny.make_root(tmp_path), CELL, 77,
                                0.5)
    assert rc == 0 and line["correct"] is False, line
    failed = [k for k, v in line["compared"].items()
              if not v["value"] <= v["limit"]]
    assert "grad1_diff" in failed and "grad1" in failed
    # the reference's own planted fault reads far above the tiny limit
    # too (on the chip at 2,048 blocks the program's reads grad1_diff
    # 0.0127 where 15 honest seeds read 0.0036-0.0039: PERF.md section 6)
    import jax
    import jax.numpy as jnp
    cfg = tiny_cfg()
    params = ref.make_params(cfg, 3)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(3), 4)
    key = jax.random.PRNGKey(1)
    _, good = ref.make_loss_and_grad(cfg, 4, 4)(params, x, y, key)
    _, bad = ref.make_loss_and_grad(cfg, 4, 4, fault="own_clean_block")(
        params, x, y, key)
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree_util.tree_leaves(bad), jax.tree_util.tree_leaves(good)))
    den = sum(float(jnp.sum(b ** 2))
              for b in jax.tree_util.tree_leaves(good))
    assert (num / den) ** 0.5 > 3 * tiny.TINY_LIMIT
    with pytest.raises(ValueError, match="fault"):
        ref.make_loss_and_grad(cfg, 4, 4, fault="no_such")


def test_the_gradient_a_layer_at_a_time_is_the_whole_programs():
    """``make_loss_and_grad`` goes through the layers one rule at a time
    so that it fits beside Adam's state at the cell's size: the same
    loss and the same gradient as ``jax.grad`` of ``loss_sum`` over the
    whole stack, in blocks of rows or whole."""
    import jax
    import jax.numpy as jnp
    cfg = tiny_cfg()
    params = ref.make_params(cfg, 4)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(4), 4)
    key = jax.random.PRNGKey(9)
    xt, m, t = ref.corrupt(jnp.asarray(x), key, cfg)
    weights = m / jnp.repeat(t, 4, axis=1)
    want_loss, want = jax.value_and_grad(ref.loss_sum)(
        jax.tree_util.tree_map(jnp.asarray, params), xt, jnp.asarray(x),
        weights, cfg)
    for block in (4, 2):
        loss, got = ref.make_loss_and_grad(cfg, 4, block)(params, x, y, key)
        assert loss == pytest.approx(float(want_loss) / x.size, rel=1e-6)
        assert jax.tree_util.tree_structure(got) \
            == jax.tree_util.tree_structure(want)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, np.asarray(w) / x.size,
                                       rtol=2e-4, atol=1e-9)


def test_half_of_one_row_is_half_of_its_positions():
    """The driver's half-batch fault at a batch of one row (``used=0``):
    the second half of the row's positions left out, the mean over the
    rest; with more rows it is the first ``used`` rows."""
    import jax
    cfg = tiny_cfg()
    params = ref.make_params(cfg, 1)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(1), 2)
    key = jax.random.PRNGKey(2)
    whole, _ = ref.make_loss_and_grad(cfg, 1, 1)(params, x[:1], y[:1], key)
    half, g = ref.make_loss_and_grad(cfg, 1, 0, used=0)(params, x[:1],
                                                        y[:1], key)
    assert half != pytest.approx(whole, rel=1e-3) and np.isfinite(half)
    one, _ = ref.make_loss_and_grad(cfg, 2, 1, used=1)(params, x, y, key)
    both, _ = ref.make_loss_and_grad(cfg, 2, 1)(params, x, y, key)
    assert one != pytest.approx(both, rel=1e-3)
