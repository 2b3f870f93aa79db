"""Latent attention (``ops/attention.LatentAttention``) and the flash
kernels at a value width of its own: through the kernels in interpret
mode and through XLA, against the dense reference and the benchmark's
plain reference; and at ``d_v == d_qk`` the kernels' launches as they
were."""

import numpy as np
import pytest


def _qkv(s, h, d, dv, seed=0, kv=None):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    kv = h if kv is None else kv
    return (jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, s, kv, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, s, kv, dv)), jnp.float32))


@pytest.mark.parametrize("s,h,kv,d,dv", [
    (256, 2, 2, 192, 128),     # the latent head: heads layout, 256 / 128 lanes
    (200, 4, 2, 64, 32),       # a ragged tail, grouped heads
    (256, 2, 1, 128, 256),     # rows layout, a value wider than the key
], ids=["latent", "ragged_grouped", "rows_wide_value"])
def test_kernels_take_a_value_width_of_its_own(monkeypatch, s, h, kv, d, dv):
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa
    from analytics_zoo_tpu.ops.attention import _reference_attention
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(s, h, d, dv, kv=kv)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(1, s, h, dv)),
                    jnp.float32)

    def dense(q, k, v):
        return _reference_attention(q, *fa.repeat_kv_heads(q, k, v),
                                    causal=True)

    with jax.default_matmul_precision("highest"):
        got = fa.flash_attention(q, k, v, True, 128, 128)
        want = dense(q, k, v)
        scan = fa.blockwise_attention(q, *fa.repeat_kv_heads(q, k, v),
                                      causal=True, block_k=64)
        g_got = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, True, 128, 128) * w), (0, 1, 2))(q, k, v)
        g_want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(
            q, k, v)
    assert got.shape == (1, s, h, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(scan), np.asarray(want),
                               atol=2e-5)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _block_shapes(q, k, v):
    """Each pallas call's operand and result block shapes in the jaxpr of
    the forward and backward of one call."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, True, 128, 128)), (0, 1, 2)))(q, k, v)
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                found.append(tuple(
                    tuple(getattr(n, "block_size", n) for n in bm.block_shape)
                    for bm in gm.block_mappings))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


def test_an_equal_value_width_keeps_the_launches(monkeypatch):
    """At ``d_v == d_qk`` every operand and result of the three launches
    takes the ``(1, block, d)`` block it took before the value had a width
    of its own (the statistics rows aside); at a narrower value, v, the
    output, its cotangent and dv take blocks of their own width and q, k,
    dq, dk theirs. The tile tables take no width at all."""
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    equal = _block_shapes(*_qkv(256, 2, 128, 128))
    assert len(equal) == 3
    for call in equal:
        assert {shape for shape in call if len(shape) == 3
                and shape[1] == 128} == {(1, 128, 128)}
    narrow = _block_shapes(*_qkv(256, 2, 192, 128))
    widths = [sorted({shape[-1] for shape in call if shape[1] == 128
                      and len(shape) == 3}) for call in narrow]
    assert widths == [[128, 256]] * 3


def test_latent_attention_through_the_kernels_and_through_xla(monkeypatch):
    """One ``LatentAttention`` (d_qk 16 = 8 + 8, d_v 8) through the flash
    kernels in interpret mode and through the dense path give the same
    output and gradient, both the benchmark reference's ``_mla`` on the
    same weights."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.attention import LatentAttention
    from benchmarks.references import bailing_hybrid as ref
    cfg = dict(num_attention_heads=2, qk_nope_head_dim=8,
               qk_rope_head_dim=8, v_head_dim=8, kv_lora_rank=16,
               rms_norm_eps=1e-6, rope_theta=6e6, rope_interleave=True,
               hidden_size=32)
    layer = LatentAttention(2, 8, 8, 8, 16, 6e6, 1e-6)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 40, 32)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2), a.shape),
        params)

    def run(p):
        out = layer.apply({"params": p}, x)
        return out, jax.grad(lambda p: jnp.sum(
            layer.apply({"params": p}, x) ** 2))(p)

    with jax.default_matmul_precision("highest"):
        dense = run(params)
        want = ref._mla(params, x, cfg, None)
        monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
        kernels = run(params)
    np.testing.assert_allclose(np.asarray(dense[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kernels[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(kernels[1]),
                    jax.tree_util.tree_leaves(dense[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-5)


def test_rope_pairs_are_interleaved_as_transformers_reorders_them():
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.attention import deinterleave
    x = jnp.arange(8.0)
    np.testing.assert_array_equal(np.asarray(deinterleave(x)),
                                  [0, 2, 4, 6, 1, 3, 5, 7])


def test_a_latent_layer_without_its_widths_is_refused():
    """A decoder config that has latent layers but leaves their widths
    unset fails at the layer: no model's widths stand in for them."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.text.hybrid_decoder import (HybridDecoder,
                                                       HybridDecoderConfig)
    cfg = HybridDecoderConfig(
        vocab=32, hidden_size=16, layer_types=("latent_attention",),
        num_heads=2, num_kv_heads=2, intermediate_size=32,
        moe_intermediate_size=8, num_dense_layers=1, num_experts=4,
        num_experts_per_tok=2, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=8)
    with pytest.raises(ValueError, match="v_head_dim"):
        HybridDecoder(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))
