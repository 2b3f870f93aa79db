"""The family ``bailing_hybrid`` as the benchmark holds it: the
configuration file against the published row and its stated cut, the
switches the module refuses, the reference's parameter layout against
``module.init``, the operation counts by hand, the expert bias's
calibration at a small size, the scopes the metric files name in the
compiled step, the entries appended to ``BENCHMARK.json``, a tiny run,
and the delta rule's erase term left out under the timed path.

The family's files lie under ``benchmarks/`` and its entries after the
parent's in ``BENCHMARK.json``'s lists: the manifest, rehearsal, faults
and scopes tests take the cell in by its name, so what they ask of every
cell is not asked again here. (``test_benchmark_new_family.py``'s first
test and ``test_benchmark_lfm2_moe.py``'s entries test ask "last" of
entries a later family follows, and fail since the second decoder family
came: PERF.md, section 7.)"""

import json
import re

import numpy as np
import pytest

from benchmarks.flops import bailing_hybrid as flops
from benchmarks.harness import tiny
from benchmarks.harness.manifest import ROOT, Cell
from benchmarks.models import bailing_hybrid as model_lib
from benchmarks.references import bailing_hybrid as ref

CELL = "ling-3.0-flash-train-b1-s8192"
CONFIG = "ling-3.0-flash"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["kda_ms.train", "kda_roofline.train"]
JOINED = ["attention_ms.train", "attention_block_roofline.train",
          "mixed_scope_share.train", "moe_ms.train",
          "moe_experts_roofline.train", "moe_local_share.train",
          "moe_load_imbalance.train", "moe_window_padding.train",
          "lm_head_ms.train", "loss_ms.train", "mlp_ms.train"]
CFG = json.loads((ROOT / "benchmarks" / "configs"
                  / f"{CONFIG}.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmarks" / "traffic"
                      / "epochs-b1-s8192.json").read_text())
#: the published config.json's widths and switches (the catalog row of
#: the model-configs guide)
PUBLISHED = dict(
    hidden_size=2560, head_dim=128, qk_head_dim=192, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512, rotary_dim=64,
    intermediate_size=6144, moe_intermediate_size=768,
    moe_shared_expert_intermediate_size=768, num_experts_per_tok=8,
    num_key_value_heads=32, n_group=8, topk_group=4, layer_group_size=6,
    short_conv_kernel_size=4, kda_lower_bound=-5, rope_theta=6000000,
    rms_norm_eps=1e-06, routed_scaling_factor=2.5, num_shared_experts=1,
    q_lora_rank=None, use_kda_lora=False, use_nGPT=False,
    use_mla_nope=False, up_proj_norm=False, value_norm=False,
    tie_word_embeddings=False, mtp_loss_scaling_factor=0,
    model_type="bailing_hybrid")
CUT = dict(num_hidden_layers=(42, 7), first_k_dense_replace=(2, 1),
           num_experts=(512, 8), num_attention_heads=(32, 8),
           vocab_size=(157184, 19648), num_nextn_predict_layers=(1, 0))


def tiny_cfg(**over) -> dict:
    return {**CFG, **model_lib.TINY, "compute_dtype": "float32", **over}


def test_every_published_width_is_unchanged_and_every_cut_is_stated():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
        assert key not in CFG["reduced"]
    for key, (published, held) in CUT.items():
        assert CFG["published"][key] == published and CFG[key] == held
        assert key in CFG["reduced"] and key in CFG["cut"]
    assert CFG["reduced"] == list(CUT)
    # the floors: a whole period, four sparse layers after the dense one,
    # 8 experts, an eighth of the vocabulary
    assert CFG["published_layers"] == [0, 6, 7, 8, 9, 10, 11]
    assert CFG["layer_types"] == ["linear_attention"] * 6 \
        + ["latent_attention"]
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert len(CFG["expert_swiglu_limit_list"]) == 42
    # the router keeps its published width; the held are ids 0-7, all in
    # routing group 0 of 8 groups of 64
    assert CFG["router_experts"] == CFG["published"]["num_experts"] == 512
    assert CFG["held_experts"] == list(range(8))
    assert max(CFG["held_experts"]) < 512 // CFG["n_group"]
    for key in ("kda_gate", "kda_norms", "mla_qk_norm", "mla_rope",
                "router", "auxiliary_loss", "expert_bias", "weights",
                "compute_dtype"):
        assert key in CFG["assumed"]
    assert "64 chips" in CFG["deployment"] and "4" in CFG["deployment"]
    assert "mtp_loss_scaling_factor" in CFG["cut"]["num_nextn_predict_layers"]
    assert CFG["calibration_batch"] == TRAFFIC["batch_size"]
    assert CFG["calibration_seq_len"] == TRAFFIC["seq_len"]


@pytest.mark.parametrize("switch", [
    dict(use_kda_lora=True), dict(use_nGPT=True), dict(use_mla_nope=True),
    dict(up_proj_norm=True), dict(value_norm=True), dict(q_lora_rank=1536),
    dict(num_nextn_predict_layers=1), dict(num_shared_experts=2),
    dict(expert_swiglu_limit_list=[0] * 11 + [4] + [0] * 30),
    dict(share_expert_swiglu_limit_list=[7] + [0] * 41),
    dict(layer_types=["linear_attention"] * 7)],
    ids=lambda d: next(iter(d)))
def test_the_module_refuses_what_it_does_not_run(switch):
    with pytest.raises(ValueError, match="not run|are not those"):
        model_lib.build_module({**CFG, **switch})
    # the published clamps lie on layers the cut does not run
    model_lib.check_runnable(CFG)
    assert CFG["expert_swiglu_limit_list"][35] == 4


def test_parameters_held_are_the_cut_models():
    import jax
    count = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        ref.param_shapes(CFG), is_leaf=lambda s: isinstance(s, tuple)))
    kda = 5 * 2560 * 1024 + 3 * 4 * 1024 + 2560 * 8 + 8 + 1024 + 128 \
        + 1024 * 2560
    mla = 2560 * 1536 + 2560 * 576 + 512 + 512 * 2048 + 2 * 192 \
        + 2560 * 8 + 1024 * 2560
    moe = 2560 * 512 + 512 + 8 * 3 * 2560 * 768 + 3 * 2560 * 768
    norms = 2 * 2560
    assert (kda, mla) == (15_762_568, 9_098_112)
    total = (kda + 3 * 2560 * 6144 + norms) + 5 * (kda + moe + norms) \
        + (mla + moe + norms) + 2 * 19648 * 2560 + 2560
    assert count == total == 577_867_952
    assert 9.2e9 < count * 16 < 0.58 * 16e9


def test_reference_layout_is_the_modules():
    import jax
    for cfg in (tiny_cfg(), CFG):
        x, _ = model_lib.make_inputs(cfg, {"seq_len": 16},
                                     np.random.default_rng(0), 1)
        variables = jax.eval_shape(lambda: model_lib.build_module(cfg).init(
            jax.random.PRNGKey(0), x))

        def flat(tree):
            return {"/".join(str(k.key) for k in path): tuple(
                        getattr(leaf, "shape", leaf))
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        tree, is_leaf=lambda s: isinstance(s, tuple))[0]}

        assert flat(variables["params"]) == flat(ref.param_shapes(cfg))


def test_inputs_are_ids_of_the_slice_and_their_next_ids():
    x, y = model_lib.make_inputs(CFG, TRAFFIC, np.random.default_rng(5), 3)
    assert x.shape == y.shape == (3, 8192) and x.dtype == np.int32
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    assert 0 <= x.min() and x.max() < CFG["vocab_size"]


def test_operation_counts_are_the_cut_models_mathematics():
    """By hand, forward, one sequence of L = 8,192 ids: six KDA layers of
    2*2560*(5*1024 + 8) + 2*1024*2560 projections, 2*4*3*1024 of
    convolutions and 8*(7*128*128 + 2*128) of recurrence a token; one
    latent layer of 2*2560*1536 + 2*2560*576 + 2*512*2048 + 2*2560*8 +
    2*1024*2560 a token and 2*8*(192 + 128) a causal pair; the dense block
    6*2560*6144 once, and six sparse layers of a router 2*2560*512, the
    shared expert 6*2560*768 and ONE routed expert in eight, 6*2560*768 / 8
    (8 of 512 picked, 8 held); the head 2*2560*19648."""
    L = 8192
    kda = 2 * 2560 * (5 * 1024 + 8) + 2 * 1024 * 2560 + 2 * 4 * 3 * 1024 \
        + 8 * (7 * 128 * 128 + 2 * 128)
    mla = 2 * 2560 * 1536 + 2 * 2560 * 576 + 2 * 512 * 2048 + 2 * 2560 * 8 \
        + 2 * 1024 * 2560
    pairs = L * (L + 1) // 2
    sparse = 2 * 2560 * 512 + 6 * 2560 * 768 + 6 * 2560 * 768 / 8
    forward = L * (6 * kda + mla + 6 * 2560 * 6144 + 6 * sparse
                   + 2 * 2560 * 19648) + pairs * 2 * 8 * 320
    assert flops.expert_rows_per_token(CFG) == 8 * 8 / 512
    assert flops.sample_flops(CFG, TRAFFIC, "train") \
        == pytest.approx(3 * forward, rel=1e-12)
    assert 12.5e12 < 3 * forward < 13.5e12
    need = flops.kda_needs(CFG, TRAFFIC, rows=1, mode="train")
    assert need["flops"] == 3 * 6 * L * kda
    assert need["bytes"] == 3 * 6 * 2 * L * 2560 * 2
    att = flops.attention_block_needs(CFG, TRAFFIC, rows=1, mode="train")
    assert att["flops"] == 3 * (L * mla + pairs * 2 * 8 * 320)
    experts = flops.moe_experts_needs(CFG, TRAFFIC, rows=1, mode="train")
    assert experts["flops"] == 3 * 6 * L / 8 * 6 * 2560 * 768
    # KDA is about a third of the step's needed operations
    assert 0.3 < need["flops"] / (3 * forward) < 0.45


def test_counts_do_not_follow_what_an_implementation_executes():
    import inspect
    body = inspect.getsource(flops).split('"""', 2)[2]
    for word in ("slack", "remat", "window", "analytics_zoo_tpu", "tile",
                 "chunk"):
        assert word not in body


def test_the_reference_imports_nothing_of_the_system():
    import inspect
    body = inspect.getsource(ref).split('"""', 2)[2]
    for word in ("analytics_zoo_tpu", "pallas", "ops.moe", "ops.kda",
                 "chunk_size"):
        assert word not in body


@pytest.mark.parametrize("seed", [2 ** 31 + 9])
def test_the_calibrated_bias_gives_the_held_experts_their_share(seed):
    """At a small size: after ``make_params`` only the expert biases
    differ from the drawn weights; on the calibration batch every one of
    64 experts' loads lies within the tolerance of the mean, and on a
    FRESH batch the held eight take about their 8/64 share."""
    import jax
    import jax.numpy as jnp
    cfg = tiny_cfg(router_experts=64, n_group=8, topk_group=4,
                   num_experts=8, held_experts=list(range(8)),
                   num_experts_per_tok=8, calibration_batch=4,
                   calibration_seq_len=256, expert_bias_tolerance=0.3,
                   hidden_size=64)
    drawn = jax.tree_util.tree_map(np.asarray, ref.draw_weights(cfg, seed))
    params = ref.make_params(cfg, seed)
    changed = sorted(
        "/".join(str(k.key) for k in path)
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_leaves(drawn)) if not np.array_equal(a, b))
    assert changed == ["block_1/moe/router/expert_bias",
                       "block_2/moe/router/expert_bias"]
    rng = np.random.default_rng(seed % 1000 + 1)
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], (4, 256),
                                   dtype=np.int32))
    p = jax.tree_util.tree_map(jnp.asarray, params)
    x = p["embed"]["embedding"][ids]
    for i, kind in enumerate(cfg["layer_types"]):
        block = p[f"block_{i}"]
        h, y = ref._operator(block, x, cfg, kind)
        if "moe" in block:
            chosen, _ = ref.route(
                ref.router_scores(block["moe"]["router"]["kernel"], y),
                block["moe"]["router"]["expert_bias"], cfg)
            share = np.isin(np.asarray(chosen), cfg["held_experts"]).mean()
            assert share == pytest.approx(8 / 64, abs=0.04)
        x = ref._ffn(block, h, y, cfg)


def test_scopes_the_metric_files_name_are_in_the_compiled_step(orca_ctx):
    """One ``fit`` at the tiny size: every pattern a joined or new metric
    file names finds an instruction of the ahead-of-time step, forward
    and backward; the delta rule's scan and the shared expert have scopes
    of their own."""
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
        "loss_ms.train", "mlp_ms.train", "optimizer_ms.train",
        "kda_ms.train", "kda_roofline.train"}
    for name, pattern in patterns.items():
        phases = {e["phase"] for e in index.values()
                  if e["scope"] and re.search(pattern, e["scope"])}
        assert phases, (name, pattern, sorted(scopes))
        if "block_" in pattern:
            assert {"forward", "backward"} <= phases, name
    for inner in (r"block_1/kda/scan(/|$)", r"block_\d+/moe/shared(/|$)"):
        assert any(re.search(inner, s) for s in scopes), inner
    # four steps of 8 x 16 tokens, 2 experts a token, two sparse layers
    grown = telemetry.snapshot()["zoo_moe_assignments_total"]
    assert sum(grown.values()) == 4 * 8 * 16 * 2 * 2
    telemetry.reset_for_tests()


def test_the_cell_reports_its_two_and_joins_its_lists():
    cell = Cell(CELL)
    reported = [m["name"] for m in cell.per_layer]
    assert tiny.in_order(NEW, reported)
    assert set(JOINED) | {"step_mfu.train", "unscoped_share.train",
                          "optimizer_ms.train"} <= set(reported)
    assert not {"ffn_ms.train", "dropout_ms.train", "conv_ms.train",
                "diffusion_masked_share.train"} & set(reported)
    assert [m["name"] for m in cell.end_to_end] == ["train_samples_per_s",
                                                    "setup_s"]
    assert set(cell.limits) == {"grad1", "grad1_diff", "dparam1",
                               "dparam_median"}
    assert cell.traffic["control"] == "fp8_e4m3" and cell.chips == 1
    assert callable(ref.fp8_e4m3)


#: what BENCHMARK.json held before this family's entries were appended
#: (the parent commit's): every one of them stands before this family's
BEFORE = {"configs": ["bert-base", "lfm2-8b-a1b", "sdar-30b-a3b"],
          "workloads": ["bert-base-train-s512", "bert-base-train-s128",
                        "bert-base-train-b32-s128",
                        "lfm2-8b-a1b-train-b2-s8192",
                        "sdar-30b-a3b-train-b1-s8192"],
          "per_layer_last": "corrupt_ms.train"}


def test_the_entries_are_appended_after_what_was_there():
    """Appended, not inserted: this family's entries stand after every
    entry the parent commit had, in their own order, and its cell's name
    is the last of each list it joined when it came (not "last" for
    good: a later family is appended after it)."""
    configs = [c["name"] for c in MANIFEST["configs"]]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert configs.index(CONFIG) > max(map(configs.index, BEFORE["configs"]))
    assert cells.index(CELL) > max(map(cells.index, BEFORE["workloads"]))
    entry = MANIFEST["workloads"][cells.index(CELL)]
    assert entry == {"name": CELL, "config": CONFIG, "chips": 1,
                     "traffic": "epochs-b1-s8192", "why": entry["why"]}
    assert "128 rows" in entry["why"] and "1/64" in entry["why"] \
        and "quarter" in entry["why"] and "7 layers" in entry["why"]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == NEW
    assert first > names.index(BEFORE["per_layer_last"])
    for key in ("per_layer", "end_to_end"):
        for m in MANIFEST[key]:
            if m["name"] in JOINED + ["train_samples_per_s"]:
                assert m["workloads"].count(CELL) == 1
                assert m["workloads"].index(CELL) > max(
                    m["workloads"].index(w) for w in BEFORE["workloads"]
                    if w in m["workloads"])
            elif m["name"] in names[:first]:
                assert CELL not in m.get("workloads", [])
    assert CELL in tiny.all_cells("train_epochs")
    config = MANIFEST["configs"][configs.index(CONFIG)]
    assert config["source"] == CFG["source"]
    assert config["reduced"] == CFG["reduced"]
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    for m in MANIFEST["per_layer"][first:first + len(NEW)]:
        assert m["workloads"] == [CELL] and m["layer"] in layers
        assert m["moves"] == "train_samples_per_s"
    assert Cell(CELL).metric_file("kda_roofline.train")["args"]["needs"] \
        == "kda_needs"


def test_a_tiny_run_is_correct(tmp_path, capsys):
    rc, line, _ = tiny.run_cell(capsys, tiny.make_root(tmp_path), CELL, 11,
                                0.5)
    assert rc == 0 and line["correct"] is True, line


# ---------------------------- the erase term left out under the timed path

def test_a_delta_rule_without_its_erase_term_is_not_correct(
        tmp_path, capsys, monkeypatch):
    """The fault this family adds: the delta rule's erase term ``-β k kᵀ``
    left out of the program's chunked rule (``S_t = D_t S_{t-1} + β_t k_t
    v_tᵀ``: ``U = Diag(β) V`` whatever the state). ``correct`` has to come
    out false, by the gradient; the reference with the same fault planted
    reads far above the tiny limit too."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import kda
    honest = kda._intra

    def no_erase(q, k, v, g, beta, sub):
        Wv, Wk, *rest = honest(q, k, v, g, beta, sub)
        return (beta[..., None] * v, jnp.zeros_like(Wk), *rest)

    monkeypatch.setattr(kda, "_intra", no_erase)
    rc, line, _ = tiny.run_cell(capsys, tiny.make_root(tmp_path), CELL, 77,
                                0.5)
    assert rc == 0 and line["correct"] is False, line
    failed = [k for k, v in line["compared"].items()
              if not v["value"] <= v["limit"]]
    assert "grad1_diff" in failed
    cfg = tiny_cfg()
    params = ref.make_params(cfg, 3)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(3), 4)
    _, good = ref.make_loss_and_grad(cfg, 4, 4)(params, x, y)
    _, bad = ref.make_loss_and_grad(cfg, 4, 4, fault="no_erase")(params, x,
                                                                  y)
    num = sum(float(np.sum((a - b) ** 2)) for a, b in zip(
        jax.tree_util.tree_leaves(bad), jax.tree_util.tree_leaves(good)))
    den = sum(float(np.sum(b ** 2)) for b in jax.tree_util.tree_leaves(good))
    assert (num / den) ** 0.5 > 3 * tiny.TINY_LIMIT
    with pytest.raises(ValueError, match="fault"):
        ref.make_loss_and_grad(cfg, 4, 4, fault="no_such")


def test_the_gradient_a_layer_at_a_time_is_the_whole_programs():
    """``make_loss_and_grad`` goes through the layers one rule at a time
    so that it fits beside Adam's state at the cell's size: the same loss
    and gradient as ``jax.grad`` of ``loss_sum`` over the whole stack, in
    blocks of rows or whole; ``used=0`` at one row takes half of its
    positions."""
    import jax
    import jax.numpy as jnp
    cfg = tiny_cfg()
    params = ref.make_params(cfg, 4)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(4), 4)
    p = jax.tree_util.tree_map(jnp.asarray, params)
    want_loss, want = jax.value_and_grad(ref.loss_sum)(p, x, y, cfg)
    for block in (2,):
        loss, got = ref.make_loss_and_grad(cfg, 4, block)(params, x, y)
        assert loss == pytest.approx(float(want_loss) / x.size, rel=1e-5)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, np.asarray(w) / x.size,
                                       rtol=2e-4, atol=1e-8)
    whole, _ = ref.make_loss_and_grad(cfg, 1, 1)(params, x[:1], y[:1])
    half, _ = ref.make_loss_and_grad(cfg, 1, 0, used=0)(params, x[:1],
                                                        y[:1])
    assert half != pytest.approx(whole, rel=1e-3) and np.isfinite(half)
