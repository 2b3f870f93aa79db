"""Kimi delta attention (``ops/kda.py``): the chunked gated delta rule
against the recurrence a token at a time, forward and gradients, and the
layer against the benchmark's plain reference."""

import numpy as np
import pytest


def _recurrence(q, k, v, g, beta, scale):
    """``S_t = (I - β_t k_t k_tᵀ) Diag(exp g_t) S_{t-1} + β_t k_t v_tᵀ``,
    ``o_t = S_tᵀ q_t · scale``, a token at a time."""
    import jax
    import jax.numpy as jnp
    b, t, h, dk = q.shape

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", S, kt)
        S = S + kt[..., None] * (bt[..., None] * (vt - seen))[:, :, None]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt) * scale

    S = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(token, S, [jnp.moveaxis(a, 1, 0)
                                     for a in (q, k, v, g, beta)])
    return jnp.moveaxis(out, 0, 1)


def _operands(t, gate_low, gate_high, seed=0, b=2, h=2, dk=8, dv=6):
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.kda import l2_normalize
    rng = np.random.default_rng(seed)
    q = l2_normalize(jnp.asarray(rng.normal(size=(b, t, h, dk)), jnp.float32))
    k = l2_normalize(jnp.asarray(rng.normal(size=(b, t, h, dk)), jnp.float32))
    v = jnp.asarray(rng.normal(size=(b, t, h, dv)), jnp.float32)
    g = jnp.asarray(rng.uniform(gate_low, gate_high, (b, t, h, dk)),
                    jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (b, t, h)), jnp.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("t,chunk,low,high", [
    (37, 16, -5.0, -4.9),     # gates pinned near the floor, a ragged tail
    (64, 64, -5.0, -4.9),
    (90, 64, -0.5, 0.0),      # slow decays: the state carries across chunks
    (48, 16, -3.0, 0.0),
], ids=["floor_16_ragged", "floor_64", "slow_64_ragged", "mixed_16"])
def test_chunked_rule_is_the_recurrence(t, chunk, low, high):
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.kda import chunked_delta_rule
    ops = _operands(t, low, high)
    scale = ops[0].shape[-1] ** -0.5
    with jax.default_matmul_precision("highest"):
        got = chunked_delta_rule(*ops, chunk=chunk)
        want = _recurrence(*ops, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-6)
        w = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                        jnp.float32)
        args = tuple(range(5))
        g_got = jax.jit(jax.grad(lambda *a: jnp.sum(
            chunked_delta_rule(*a, chunk=chunk) * w), args))(*ops)
        g_want = jax.jit(jax.grad(lambda *a: jnp.sum(
            _recurrence(*a, scale) * w), args))(*ops)
    for name, a, b in zip("qkvgβ", g_got, g_want):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-3,
                                   atol=1e-4 * np.abs(b).max(), err_msg=name)


def test_decays_at_the_floor_are_taken_as_ratios():
    """At a gate of -5 a position, ``exp(G)`` over one chunk of 64
    underflows float32 and ``exp(-G)`` overflows: a form that multiplies
    the two apart gives 0 * inf. The chunked rule stays finite and equal
    to the recurrence with every gate at the floor."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.kda import chunked_delta_rule
    G = jnp.cumsum(jnp.full((64,), -5.0, jnp.float32))
    assert float(jnp.exp(G[-1])) == 0.0
    assert not np.isfinite(float(jnp.exp(-G[-1])))
    ops = _operands(70, -5.0, -5.0, seed=3)
    got = np.asarray(chunked_delta_rule(*ops, chunk=64))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, np.asarray(_recurrence(*ops, ops[0].shape[-1] ** -0.5)),
        rtol=1e-4, atol=2e-6)


def test_a_chunk_is_a_multiple_of_the_sub_chunk():
    from analytics_zoo_tpu.ops.kda import chunked_delta_rule
    with pytest.raises(ValueError, match="multiple"):
        chunked_delta_rule(*_operands(8, -1, 0), chunk=24)


def _layer_params(cfg, seed):
    import jax
    from benchmarks.references import bailing_hybrid as ref
    params = ref.draw_weights(cfg, seed)
    return jax.tree_util.tree_map(np.asarray, params["block_0"]["kda"])


def _cfg(**over):
    cfg = dict(hidden_size=32, num_attention_heads=2, head_dim=16,
               short_conv_kernel_size=4, kda_lower_bound=-5,
               rms_norm_eps=1e-6, qk_nope_head_dim=8, qk_rope_head_dim=8,
               v_head_dim=8, kv_lora_rank=16, intermediate_size=8,
               moe_intermediate_size=8, num_shared_experts=1,
               moe_shared_expert_intermediate_size=8, router_experts=4,
               held_experts=[0], vocab_size=8, first_k_dense_replace=1,
               layer_types=["linear_attention"], initializer_range=0.3)
    cfg.update(over)
    return cfg


def test_the_layer_is_the_references():
    """``KimiDeltaAttention`` in float32 against the benchmark's plain
    reference (the recurrence a token at a time), on the reference's
    seeded weights: projections, convolutions, norms, gate and output
    gate as the configuration's ``assumed`` states them."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.kda import KimiDeltaAttention
    from benchmarks.references import bailing_hybrid as ref
    cfg = _cfg()
    p = _layer_params(cfg, 4)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 40, 32)),
                    jnp.float32)
    layer = KimiDeltaAttention(2, 16)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: layer.apply({"params": p}, x))(p, x)
        want = jax.jit(lambda p, x: ref._kda(p, x, cfg, None))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    shapes = jax.tree_util.tree_map(
        np.shape, layer.init(jax.random.PRNGKey(0), x)["params"])
    assert shapes == jax.tree_util.tree_map(np.shape, p)


def test_the_scan_is_one_named_scope():
    import jax
    from analytics_zoo_tpu.ops.kda import chunked_delta_rule
    ops = _operands(20, -1, 0)
    text = jax.jit(chunked_delta_rule).lower(*ops).as_text(
        debug_info=True)
    assert "scan/" in text
