"""The hybrid decoder (``text/hybrid_decoder.py``) and its operators —
gated short convolution, grouped-query attention with rotary positions,
the dropless expert layer told which experts it holds — against the plain
reference of the benchmark's family ``lfm2_moe``, at the tiny size, in
float32, on seeded random weights."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common import profiling, telemetry
from analytics_zoo_tpu.learn import losses
from analytics_zoo_tpu.ops import attention as attention_lib
from analytics_zoo_tpu.ops import moe as moe_lib
from analytics_zoo_tpu.ops import short_conv
from analytics_zoo_tpu.ops.flash_attention import RESIDUAL_NAMES
from analytics_zoo_tpu.text import hybrid_decoder
from benchmarks.harness import program
from benchmarks.harness.manifest import ROOT
from benchmarks.models import lfm2_moe as model_lib
from benchmarks.references import lfm2_moe as ref


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def tiny_cfg(**over) -> dict:
    cfg = json.loads((ROOT / "benchmarks" / "configs"
                      / "lfm2-8b-a1b.json").read_text())
    cfg.update(model_lib.TINY, compute_dtype="float32")
    cfg.update(over)
    return cfg


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def leaves(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------- program vs reference

def test_parameter_tree_is_the_references_layout():
    cfg = tiny_cfg()
    x, _ = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(0), 2)
    variables = jax.eval_shape(
        lambda: model_lib.build_module(cfg).init(jax.random.PRNGKey(0), x))
    got = {k: v.shape for k, v in leaves(variables["params"]).items()}
    want = leaves(jax.tree_util.tree_map(
        lambda s: np.zeros(s), ref.param_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple)))
    assert got == {k: v.shape for k, v in want.items()}


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7])
def test_logits_and_loss_agree_with_the_reference(seed):
    cfg = tiny_cfg()
    params = ref.make_params(cfg, seed)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(seed), 4)
    logits = model_lib.build_module(cfg).apply({"params": params}, x)
    want = ref.forward(params, x, cfg)
    assert logits.shape == (4, 16, cfg["vocab_size"])
    assert rel(logits, want) < 1e-5
    loss = losses.get(model_lib.LOSS)(y, logits).mean()
    assert float(loss) == pytest.approx(
        float(ref.loss_sum(params, x, y, cfg)) / y.size, rel=1e-5)


def test_every_leafs_gradient_through_fits_own_step(orca_ctx):
    """One optimizer step of ``fit`` (the benchmark's own build): Adam's
    first moment over 1 - b1 is the step's gradient; the loss it reports
    is the reference's; both blocked and whole."""
    cfg = tiny_cfg()
    seed = 11
    params = ref.make_params(cfg, seed)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(seed), 8)
    est = program.build_estimator(
        model_lib.build_module(cfg), model_lib.LOSS,
        {"name": "adam", "learningrate": 1e-5}, params, x[:2])
    hist = est.fit((x, y), epochs=1, batch_size=8, shuffle=False)
    got = jax.tree_util.tree_map(lambda m: np.asarray(m) / 0.1,
                                 program.first_moment(est))
    for block in (8, 2):
        loss, want = ref.make_loss_and_grad(cfg, 8, block)(params, x, y)
        assert hist["loss"][-1] == pytest.approx(loss, rel=1e-5)
        got_leaves, want_leaves = leaves(got), leaves(want)
        assert set(got_leaves) == set(want_leaves)
        for name, w in want_leaves.items():
            if name.endswith("expert_bias"):
                assert not np.any(w) and not np.any(got_leaves[name])
            else:
                assert rel(got_leaves[name], w) < 2e-4, name


def test_labels_of_batch_by_seq_in_blocks_as_whole(monkeypatch):
    """The float32 copy of ``[batch, seq, vocab]`` logits taken a block of
    positions at a time: the same loss and the same gradient."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(4, 24, 50)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 50, (4, 24)))
    fn = losses.sparse_categorical_crossentropy_from_logits

    def both():
        return jax.value_and_grad(lambda z: fn(labels, z).mean())(logits)

    whole = both()
    monkeypatch.setattr(losses, "LOGITS_BLOCK_BYTES", 24 * 50 * 4)
    blocked = both()          # 4 blocks of 24 positions
    assert whole[0].shape == () and fn(labels, logits).shape == (4,)
    np.testing.assert_allclose(blocked[0], whole[0], rtol=1e-6)
    np.testing.assert_allclose(blocked[1], whole[1], rtol=1e-5, atol=1e-9)
    lowered = jax.jit(lambda z: fn(labels, z)).lower(logits).as_text()
    assert "while" in lowered
    # bf16 logits are widened inside the blocks, the result is float32
    assert fn(labels, logits.astype(jnp.bfloat16)).dtype == jnp.float32


# ----------------------------------------------------- the expert layer

def _layer(cfg, held, params):
    """The program's layer holding ``held``, with the reference's weights
    of those experts."""
    module = moe_lib.DroplessMoE(
        cfg["router_experts"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"], tuple(held))
    idx = np.asarray(held)
    mine = {"router": params["router"],
            "experts": {k: np.asarray(v)[idx]
                        for k, v in params["experts"].items()}}
    return module, mine


def _moe_params(cfg, seed, bias=None):
    everything = tiny_cfg(num_experts=cfg["router_experts"],
                          held_experts=list(range(cfg["router_experts"])))
    p = ref.draw_weights(everything, seed)["block_1"]["moe"]
    p = jax.tree_util.tree_map(np.asarray, p)
    if bias is not None:
        p["router"]["expert_bias"] = np.asarray(bias, np.float32)
    return everything, p


def test_shares_of_a_sparse_layer_add_up_to_the_uncut_reference_layer():
    cfg = tiny_cfg()
    everything, p = _moe_params(cfg, 5)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg["hidden_size"]))
    want = ref._moe(p, x, everything, None)
    total, held_counts = 0, 0
    for held in ([0, 1], [2, 3], [4, 5], [6, 7]):
        module, mine = _layer(cfg, held, p)
        y, mut = module.apply({"params": mine}, x, mutable=["counters"])
        total = total + y
        share = ref._moe(jax.tree_util.tree_map(jnp.asarray, mine), x,
                         dict(everything, held_experts=held), None)
        assert rel(y, share) < 1e-5
        held_counts += int(
            mut["counters"]["zoo_moe_assignments_total{held=true}"])
    assert rel(total, want) < 1e-5
    assert held_counts == 2 * 16 * cfg["num_experts_per_tok"]
    # held = all is the published layer
    module, mine = _layer(cfg, range(8), p)
    assert rel(module.apply({"params": mine}, x), want) < 1e-5


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """Every assignment lands on the two held experts: four times the
    rows the first window holds, so the second runs; nothing is lost,
    forward or backward."""
    cfg = tiny_cfg()
    bias = np.zeros(8, np.float32)
    bias[[2, 5]] = 10.0
    everything, p = _moe_params(cfg, 6, bias)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 64, cfg["hidden_size"]))
    module, mine = _layer(cfg, [2, 5], p)
    held_cfg = dict(everything, held_experts=[2, 5])

    def prog(params, x):
        return jnp.sum(jnp.square(module.apply({"params": params}, x)))

    def plain(params, x):
        return jnp.sum(jnp.square(ref._moe(params, x, held_cfg, None)))

    y, mut = module.apply({"params": mine}, x, mutable=["counters"])
    c = mut["counters"]
    assert int(c["zoo_moe_assignments_total{held=true}"]) == 8 * 64 * 2
    assert int(c["zoo_moe_assignments_total{held=false}"]) == 0
    assert float(c["zoo_moe_load_imbalance"]) == pytest.approx(4.0)
    # the first window (512 rows of the 1,024 assignments) is full
    assert int(c["zoo_moe_window_rows_total{used=true}"]) == 512
    assert int(c["zoo_moe_window_rows_total{used=false}"]) == 0
    want = ref._moe(jax.tree_util.tree_map(jnp.asarray, mine), x, held_cfg,
                    None)
    assert rel(y, want) < 1e-5
    assert rel(y, ref._moe(p, x, everything, None)) < 1e-5   # all of it
    got = jax.grad(prog, argnums=(0, 1))(mine, x)
    ref_grads = jax.grad(plain, argnums=(0, 1))(
        jax.tree_util.tree_map(jnp.asarray, mine), x)
    for name, w in leaves(ref_grads).items():
        if not name.endswith("expert_bias"):
            assert rel(leaves(got)[name], w) < 1e-4, name


def test_a_token_none_of_whose_experts_is_held_gets_nothing():
    cfg = tiny_cfg()
    bias = np.zeros(8, np.float32)
    bias[[0, 1]] = 10.0                 # every token picks 0 and 1
    _, p = _moe_params(cfg, 7, bias)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg["hidden_size"]))
    module, mine = _layer(cfg, [6, 7], p)
    y, mut = module.apply({"params": mine}, x, mutable=["counters"])
    assert not np.any(np.asarray(y))
    assert int(mut["counters"]["zoo_moe_assignments_total{held=true}"]) == 0
    # every row of the window (all 2 x 16 x 2 at this size) was padding
    c = mut["counters"]
    assert int(c["zoo_moe_window_rows_total{used=true}"]) == 0
    assert int(c["zoo_moe_window_rows_total{used=false}"]) == 64


def test_the_first_windows_unused_rows_are_counted():
    """1,024 tokens x 2 over 2 of 8 experts: an even routing holds 512
    assignments, the window is 1.25 times that rounded up to 512s."""
    cfg = tiny_cfg()
    _, p = _moe_params(cfg, 8)
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (4, 256, cfg["hidden_size"]))
    module, mine = _layer(cfg, [0, 1], p)
    _, mut = module.apply({"params": mine}, x, mutable=["counters"])
    c = mut["counters"]
    held = int(c["zoo_moe_assignments_total{held=true}"])
    assert 0 < held < 1024
    assert int(c["zoo_moe_window_rows_total{used=true}"]) == held
    assert int(c["zoo_moe_window_rows_total{used=false}"]) == 1024 - held


def test_the_bias_steers_the_selection_and_carries_no_gradient():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]])
    ids, w = moe_lib.sigmoid_top_k_routing(logits, jnp.zeros(4), 2)
    assert sorted(np.asarray(ids)[0]) == [2, 3]
    s = jax.nn.sigmoid(jnp.asarray([3.0, 2.0]))
    np.testing.assert_allclose(np.asarray(w)[0], s / (s.sum() + 1e-6),
                               rtol=1e-6)
    bias = jnp.asarray([5.0, 0.0, 0.0, 0.0])
    ids, w = moe_lib.sigmoid_top_k_routing(logits, bias, 2)
    assert sorted(np.asarray(ids)[0]) == [0, 3]
    s = jax.nn.sigmoid(jnp.asarray([0.0, 3.0]))      # the scores, unbiased
    by_id = dict(zip(np.asarray(ids)[0].tolist(), np.asarray(w)[0]))
    np.testing.assert_allclose([by_id[0], by_id[3]], s / (s.sum() + 1e-6),
                               rtol=1e-6)
    g = jax.grad(lambda b: moe_lib.sigmoid_top_k_routing(
        logits, b, 2)[1].sum())(bias)
    assert not np.any(np.asarray(g))


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_bias_calibration_brings_every_expert_within_its_tolerance(seed):
    """On the calibration batch itself, layer after layer, through the
    reference's forward pass; a zero bias does not."""
    cfg = tiny_cfg(calibration_batch=8, calibration_seq_len=128,
                   expert_bias_tolerance=0.05)
    params = ref.make_params(cfg, seed)
    rng = np.random.default_rng([seed % (2 ** 31), 0xCA11B])
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], (8, 128),
                                   dtype=np.int32))
    x = jnp.asarray(params["embed"]["embedding"])[ids]
    k, e = cfg["num_experts_per_tok"], cfg["router_experts"]
    mean = ids.size * k / e
    off_without = []
    for i, kind in enumerate(cfg["layer_types"]):
        block = jax.tree_util.tree_map(jnp.asarray, params[f"block_{i}"])
        h, y = ref._operator(block, x, cfg, kind)
        if "moe" in block:
            scores = ref.router_scores(block["moe"]["router"]["kernel"], y)
            bias = block["moe"]["router"]["expert_bias"]
            assert np.any(np.asarray(bias))

            def worst(b):
                _, picked = jax.lax.top_k(scores + b, k)
                loads = np.bincount(np.asarray(picked).ravel(), minlength=e)
                return float(np.max(np.abs(loads / mean - 1)))

            assert worst(bias) <= 0.05
            off_without.append(worst(0.0))
        x = ref._ffn(block, h, y, cfg)
    assert max(off_without) > 0.05
    # the same seed gives the same bias; another seed another
    again = ref.make_params(cfg, seed)
    other = ref.make_params(cfg, seed + 1)
    at = ("block_1", "moe", "router", "expert_bias")

    def leaf(tree):
        for key in at:
            tree = tree[key]
        return tree

    np.testing.assert_array_equal(leaf(again), leaf(params))
    assert np.any(leaf(other) != leaf(params))


# ------------------------------------------- convolution and attention

def test_short_convolution_is_causal_and_follows_the_reference():
    cfg = tiny_cfg()
    p = jax.tree_util.tree_map(
        jnp.asarray, ref.draw_weights(cfg, 3)["block_0"]["conv"])
    module = short_conv.GatedShortConv(cfg["conv_L_cache"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg["hidden_size"]))
    y = module.apply({"params": p}, x)
    assert rel(y, ref._conv(p, x, cfg, None)) < 1e-5
    t = 9
    moved = module.apply({"params": p}, x.at[:, t].add(1.0))
    np.testing.assert_array_equal(np.asarray(moved[:, :t]),
                                  np.asarray(y[:, :t]))
    assert np.any(np.asarray(moved[:, t]) != np.asarray(y[:, t]))
    # three taps: position t + 3 and later see position t only through
    # nothing at all
    np.testing.assert_array_equal(np.asarray(moved[:, t + 3:]),
                                  np.asarray(y[:, t + 3:]))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 6, 4))
    taps = jnp.asarray([[1.0] * 4, [10.0] * 4, [100.0] * 4])
    want = v + 10 * jnp.pad(v, ((0, 0), (1, 0), (0, 0)))[:, :6] \
        + 100 * jnp.pad(v, ((0, 0), (2, 0), (0, 0)))[:, :6]
    np.testing.assert_allclose(short_conv.causal_depthwise_conv(v, taps),
                               want, rtol=1e-6)


def test_grouped_attention_is_causal_and_follows_the_reference():
    cfg = tiny_cfg()
    p = jax.tree_util.tree_map(
        jnp.asarray, ref.draw_weights(cfg, 4)["block_1"]["attention"])
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    module = attention_lib.GroupedQueryAttention(
        h, g, cfg["hidden_size"] // h, float(cfg["rope_theta"]),
        cfg["norm_eps"])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg["hidden_size"]))
    y = module.apply({"params": p}, x)
    assert rel(y, ref._attention(p, x, cfg, None)) < 1e-5
    t = 9
    moved = module.apply({"params": p}, x.at[:, t].add(1.0))
    np.testing.assert_allclose(np.asarray(moved[:, :t]),
                               np.asarray(y[:, :t]), rtol=0, atol=1e-7)
    assert np.all(np.any(np.asarray(moved[:, t:]) != np.asarray(y[:, t:]),
                         axis=-1))


def test_each_key_value_head_serves_its_consecutive_query_heads():
    q = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(8), (1, 8, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(9), (1, 8, 2, 8))
    got = attention_lib.grouped_query_attention(q, k, v)
    for head in range(4):
        want = attention_lib._reference_attention(
            q[:, :, head:head + 1], k[:, :, head // 2:head // 2 + 1],
            v[:, :, head // 2:head // 2 + 1], causal=True)
        np.testing.assert_allclose(got[:, :, head:head + 1], want,
                                   rtol=1e-5, atol=1e-6)


def test_rotary_positions_rotate_the_halves():
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 5, 2, 8))
    got = attention_lib.rotary_embedding(x, 1e6)
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)   # angle 0
    pos, i = 3, 1
    angle = pos / (1e6 ** (2 * i / 8))
    a, b = x[0, pos, 0, i], x[0, pos, 0, i + 4]
    np.testing.assert_allclose(
        got[0, pos, 0, i], a * np.cos(angle) - b * np.sin(angle), rtol=1e-5)
    np.testing.assert_allclose(
        got[0, pos, 0, i + 4], b * np.cos(angle) + a * np.sin(angle),
        rtol=1e-5)
    # scores depend on the distance alone
    q = attention_lib.rotary_embedding(jnp.ones((1, 6, 1, 8)), 100.0)
    scores = jnp.einsum("bqhd,bkhd->qk", q, q)
    np.testing.assert_allclose(scores[1, 0], scores[5, 4], rtol=1e-5)


def test_an_untuned_long_sequence_takes_the_kernel_not_the_scan(monkeypatch):
    """No verdict, a TPU, float32 scores beyond the switch: the blockwise
    scan's backward keeps every block's probabilities, so the kernels run
    at their untuned block sizes."""
    from analytics_zoo_tpu.ops import autotune, flash_attention
    calls = []
    monkeypatch.setattr(autotune, "on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "attention_decision", lambda *a: None)
    monkeypatch.setattr(
        flash_attention, "flash_attention",
        lambda q, k, v, causal, bq, bk, mask: calls.append(
            (causal, bq, bk)) or q)
    monkeypatch.setattr(
        flash_attention, "blockwise_attention",
        lambda q, k, v, causal=False, mask=None: calls.append("scan") or q)
    long = jnp.zeros((1, 32768, 1, 8), jnp.bfloat16)
    autotune.auto_flash_attention(long, long, long, causal=True)
    assert calls == [(True,) + autotune.UNTUNED_BLOCKS]
    short = jnp.zeros((1, 1024, 1, 8), jnp.bfloat16)
    autotune.auto_flash_attention(short, short, short, causal=True)
    assert calls[-1] == "scan"


# ------------------------- what a rematerialised layer keeps by name

def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs in its equations'
    parameters (``checkpoint``, ``custom_vjp_call``, ``pjit`` ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _count(fn, *args, primitive: str) -> int:
    return sum(e.primitive.name == primitive
               for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr))


def _attention_layer(dtype):
    """``tanh(x @ w) -> flash_attention(causal) -> @ w``: the kernel
    between two products, as in a block of the decoder."""
    from analytics_zoo_tpu.ops.flash_attention import flash_attention
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (1, 256, 2, 64), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (64, 64), jnp.float32) / 8).astype(dtype)

    def layer(x, w):
        y = jnp.tanh(x @ w)
        return flash_attention(y, y, y, True, 128, 128) @ w

    return layer, x, w


def _grad_of(layer, policy):
    if policy != "no checkpoint":
        layer = jax.checkpoint(layer, policy=policy)
    return jax.grad(
        lambda x, w: (layer(x, w).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1))


@pytest.mark.parametrize("policy,launches", [
    ("no checkpoint", 3), (hybrid_decoder._products_saveable, 4),
    (hybrid_decoder._BLOCK_POLICY, 3)],
    ids=["no_checkpoint", "products_alone", "the_decoders_policy"])
def test_the_forward_kernel_is_launched_once_where_its_residuals_are_kept(
        monkeypatch, policy, launches):
    """Forward, dq and dk/dv kernels: three launches in a gradient. A
    checkpoint that keeps products alone launches the forward kernel a
    second time for the backward's ``out`` and ``lse``."""
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    layer, x, w = _attention_layer(jnp.float32)
    assert _count(_grad_of(layer, policy), x, w,
                  primitive="pallas_call") == launches


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kept_residuals_give_the_unrematerialised_gradient_exactly(
        monkeypatch, dtype):
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    layer, x, w = _attention_layer(dtype)
    want = jax.jit(_grad_of(layer, "no checkpoint"))(x, w)
    got = jax.jit(_grad_of(layer, hybrid_decoder._BLOCK_POLICY))(x, w)
    for g, r in zip(got, want):
        assert np.asarray(r, np.float32).any()
        assert np.array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("primitive,params,kept", [
    ("name", {"name": RESIDUAL_NAMES[0]}, True),
    ("name", {"name": RESIDUAL_NAMES[1]}, True),
    ("name", {"name": "some_other_value"}, False),
    ("dot_general", {}, True), ("ragged_dot_general", {}, True),
    ("exp", {}, False), ("pallas_call", {}, False)],
    ids=["out", "lse", "another_name", "dot_general", "ragged_dot_general",
         "exp", "pallas_call"])
def test_a_block_keeps_its_products_and_the_kernels_residuals_only(
        primitive, params, kept):
    """The decoder's policy, asked about one equation."""
    from jax._src.lax import lax as lax_internal
    from jax._src.pallas.pallas_call import pallas_call_p
    from jax.extend.core import primitives
    prim = {"name": primitives.name_p,
            "dot_general": primitives.dot_general_p,
            "ragged_dot_general": lax_internal.ragged_dot_general_p,
            "exp": primitives.exp_p, "pallas_call": pallas_call_p}[primitive]
    assert prim.name == primitive
    assert hybrid_decoder._BLOCK_POLICY(prim, **params) is kept


def test_every_block_is_rematerialised_under_that_policy(monkeypatch):
    import flax.linen as nn
    handed = []
    monkeypatch.setattr(
        nn, "remat", lambda cls, policy: handed.append(policy) or cls)
    module = model_lib.build_module(tiny_cfg())
    jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert handed == [hybrid_decoder._BLOCK_POLICY]


def test_outside_differentiation_nothing_is_named(monkeypatch):
    """``checkpoint_name`` sits in the ``custom_vjp``'s forward rule: the
    primal function (``predict``, the tuner's timing) traces to the
    kernel alone."""
    from analytics_zoo_tpu.ops.flash_attention import flash_attention
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    _, x, _ = _attention_layer(jnp.bfloat16)

    def primal(x):
        return flash_attention(x, x, x, True, 128, 128)

    assert _count(primal, x, primitive="name") == 0
    assert _count(primal, x, primitive="pallas_call") == 1
    assert _count(jax.grad(lambda x: primal(x).astype(jnp.float32).sum()),
                  x, primitive="name") == 2


# ------------------ the layer on the kernel path: no copy of a head's data

@pytest.mark.parametrize("head_dim,layout", [(128, "rows"), (64, "heads")])
def test_the_kernel_path_copies_no_heads_data(monkeypatch, head_dim, layout):
    """The jaxpr of ``GroupedQueryAttention``'s gradient where the kernels
    run (a TPU, no verdict, scores beyond the switch): three flash
    launches that say ``grouped`` and the layout the head's width picks;
    k and v are never repeated to the query heads (no ``broadcast_in_dim``
    to ``[b, s, kv_heads, groups, d]``), and at a head of whole lanes no
    ``[b, s, h, d]`` operand is transposed head-major on either side of a
    launch. There the q/k norm and rotary positions are the norm-rotary
    kernels, a forward launch for q and one for k and a backward each, and
    nothing computes on q or k as ``[b, s, heads, d]``: the four-dimensional
    view is reshapes alone. At 64 lanes the XLA chain stays and no
    norm-rotary kernel is launched. Off the kernel path the same layer
    repeats k and v, once."""
    from analytics_zoo_tpu.ops import autotune, flash_attention
    b, s, h, kv, hidden = 1, 256, 4, 2, 32
    layer = attention_lib.GroupedQueryAttention(h, kv, head_dim)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(b, s, hidden)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    heads_of_q_or_k = {(b, s, h, head_dim), (b, s, kv, head_dim)}

    def seen():
        def grad(params, x):          # traced anew: jax keeps a jaxpr by
            return jax.grad(          # the function it was made from
                lambda p, x: layer.apply(p, x).sum(),
                argnums=(0, 1))(params, x)
        found = list(_eqns(jax.make_jaxpr(grad)(params, x).jaxpr))
        kernels = [(e.params["jaxpr"].debug_info.func_name,
                    e.params["metadata"]) for e in found
                   if e.primitive.name == "pallas_call"]
        return ([dict(said) for name, said in kernels
                 if name.startswith("_flash_")],
                sorted(name for name, _ in kernels
                       if name.startswith("_norm_rotary_")),
                [e for e in found if e.primitive.name == "broadcast_in_dim"
                 and e.outvars[0].aval.shape
                 == (b, s, kv, h // kv, head_dim)],
                [e for e in found if e.primitive.name == "transpose"
                 and len(e.invars[0].aval.shape) == 4],
                {e.primitive.name for e in found
                 if e.primitive.name not in ("reshape", "name")
                 and any(getattr(o.aval, "shape", None) in heads_of_q_or_k
                         for o in e.outvars)})

    launches, norm_rotary, repeats, transposes, on_heads = seen()
    assert not launches and not norm_rotary and len(repeats) == 2
    assert {"mul", "concatenate"} <= on_heads            # the chain
    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "attention_decision", lambda *a: None)
    monkeypatch.setattr(autotune, "SCORES_SWITCH", 0)
    launches, norm_rotary, repeats, transposes, on_heads = seen()
    assert len(launches) == 3
    assert all(said["layout"] == layout and said["kv"] == "grouped"
               for said in launches)
    assert not repeats
    assert bool(transposes) == (layout == "heads")
    if layout == "rows":
        assert norm_rotary == ["_norm_rotary_bwd_kernel"] * 2 \
            + ["_norm_rotary_fwd_kernel"] * 2
        assert not on_heads
    else:
        assert not norm_rotary
        assert {"mul", "concatenate", "optimization_barrier"} <= on_heads


# ------------------------------------------------ tracing and counters

def test_scopes_and_counters_of_the_step(orca_ctx):
    import re
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
    for wanted in (r"block_0/conv(/|$)", r"block_2/conv(/|$)",
                   r"block_1/attention(/|$)", r"block_0/mlp(/|$)",
                   r"block_1/moe/router(/|$)", r"block_2/moe/router(/|$)",
                   r"block_1/moe/experts(/|$)", r"(^|/)lm_head(/|$)",
                   r"^optimizer(/|$)", r"^loss(/|$)"):
        phases = {e["phase"] for e in index.values()
                  if e["scope"] and re.search(wanted, e["scope"])}
        assert phases, (wanted, sorted(scopes))
        if "block_" in wanted:
            assert {"forward", "backward"} <= phases, wanted
    snap = telemetry.snapshot()
    total = snap["zoo_moe_assignments_total"]
    steps, per_step = 4, 8 * 16 * cfg["num_experts_per_tok"]
    for layer in ("block_1/moe", "block_2/moe"):
        assert total[f"held=true,layer={layer}"] \
            + total[f"held=false,layer={layer}"] == steps * per_step
        assert 0 < total[f"held=true,layer={layer}"] < steps * per_step
        assert snap["zoo_moe_load_imbalance"][f"layer={layer}"] >= 1.0
        # at this size the window is all the assignments
        rows = snap["zoo_moe_window_rows_total"]
        assert rows[f"layer={layer},used=false"] \
            == total[f"held=false,layer={layer}"]
        assert rows[f"layer={layer},used=true"] \
            == total[f"held=true,layer={layer}"]
    text = telemetry.prometheus_text()
    assert 'zoo_moe_assignments_total{held="true",layer="block_1/moe"}' \
        in text
    # nothing of a step's counters rides the model's state
    assert "counters" not in est._state["model_state"]


def test_publish_step_counters_grows_totals_and_keeps_the_last_gauge():
    steps = [{"a": {"b": {"n_total{kind=x}": np.int32(3), "level": 1.5}}},
             {"a": {"b": {"n_total{kind=x}": np.int32(4), "level": 2.5}}}]
    telemetry.publish_step_counters(steps)
    telemetry.publish_step_counters(steps[:1])
    snap = telemetry.snapshot()
    assert snap["n_total"] == {"kind=x,layer=a/b": 10.0}
    assert snap["level"] == {"layer=a/b": 1.5}
    with pytest.raises(ValueError, match="not a series name"):
        telemetry.publish_step_counters([{"a": {"no good": 1}}])


def test_a_kernel_the_compiler_named_takes_the_scope_of_what_feeds_it():
    """The TPU's grouped matrix product arrives as a custom-call whose
    op_name is the kernel's own name: it counts with the instruction that
    made its operand, not with the optimizer that reads a weight
    gradient."""
    hlo = (
        "ENTRY %main (a: f32[4]) -> f32[4] {\n"
        "  %a = f32[4]{0} parameter(0)\n"
        "  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, "
        'calls=%f1, metadata={op_name="jit(step_fn)/transpose(jvp(M))/'
        'block_1/moe/experts/products/select_n"}\n'
        "  %ragged-dot-none.2 = f32[4]{0} custom-call(f32[4]{0} %fusion.1),"
        ' custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}\n'
        "  %fusion.4 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, "
        'calls=%f4, metadata={op_name="gather"}\n'
        "  %fusion.5 = f32[4]{0} fusion(f32[4]{0} %fusion.4), kind=kLoop, "
        'calls=%f5, metadata={op_name="jit(step_fn)/jvp(M)/block_1/moe/'
        'router/mul"}\n'
        "  ROOT %fusion.3 = f32[4]{0} fusion(f32[4]{0} %ragged-dot-none.2), "
        'kind=kLoop, calls=%f3, metadata={op_name="jit(step_fn)/optimizer/'
        'add"}\n}\n')
    index = profiling.parse_scope_index(hlo)
    # a gather the compiler rewrote and named "gather": with its reader
    assert index["fusion.4"]["scope"] == "M/block_1/moe/router"
    assert index["fusion.4"]["phase"] == "forward"
    assert index["ragged-dot-none.2"] == {
        "scope": "M/block_1/moe/experts/products", "phase": "backward",
        "scopes": [], "opcode": "custom-call", "kernel": "ragged_dot"}
    assert index["fusion.3"]["scope"] == "optimizer"
