"""Every ``pl.pallas_call`` in ``ops/`` must pass the TPU lowering.

``jax.export`` with ``platforms=("tpu",)`` runs the Pallas → Mosaic lowering
on the CPU, no chip needed. It is the stage that refuses a block whose last
two dims are neither (8, 128)-divisible nor the whole array's — the class
that once kept the flash backward, paged attention and both embedding
kernels off the chip while the interpreter-mode parity tests stayed green.
Shapes are ``chip_smoke.py``'s: what lowers here is what the smoke then
compiles and checks on the device. (Mosaic's own compile still needs the
chip; this guards the stage before it.)
"""

import base64
import re

import jax
import jax.numpy as jnp
import pytest
from jax import export

from analytics_zoo_tpu.ops import embedding_bag as eb
from analytics_zoo_tpu.ops import flash_attention as fa
from analytics_zoo_tpu.ops import norm_rotary as nr
from analytics_zoo_tpu.ops import paged_attention as pa

S = jax.ShapeDtypeStruct


def lowers_for_tpu(fn, *avals):
    exported = export.export(jax.jit(fn), platforms=("tpu",))(*avals)
    assert "tpu_custom_call" in exported.mlir_module(), \
        "no pallas kernel in the lowered module: the reference ran instead"


def _sq(a):
    return (a.astype(jnp.float32) ** 2).sum()


FLASH_SHAPES = [
    pytest.param(32, 128, 12, 64, False, 12, id="bert-base-b32s128h12d64"),
    pytest.param(2, 2048, 8, 128, True, 8, id="b2s2048h8d128-causal"),
    # a key/value head read by its group, in both operand layouts
    pytest.param(2, 2048, 8, 128, True, 2, id="b2s2048h8kv2d128-causal"),
    pytest.param(2, 2048, 8, 64, True, 2, id="b2s2048h8kv2d64-causal"),
    # the hybrid decoder's attention layer at its cell's size: 32 query
    # heads over 8 key/value heads
    pytest.param(2, 8192, 32, 64, True, 8, id="gqa-b2s8192h32kv8d64-causal"),
    # ragged sequence, unaligned head dim, block_q clamped below the lane
    pytest.param(1, 200, 2, 64, True, 2, id="ragged-s200"),
    pytest.param(1, 40, 2, 64, False, 2, id="short-s40"),
]


def _qkv(b, s, h, d, kv):
    return (S((b, s, h, d), jnp.bfloat16),) \
        + (S((b, s, kv, d), jnp.bfloat16),) * 2


@pytest.mark.parametrize("b,s,h,d,causal,kv", FLASH_SHAPES)
def test_flash_forward_lowers(b, s, h, d, causal, kv):
    lowers_for_tpu(lambda q, k, v: fa.flash_attention(q, k, v, causal),
                   *_qkv(b, s, h, d, kv))


@pytest.mark.parametrize("b,s,h,d,causal,kv", FLASH_SHAPES)
def test_flash_grad_lowers(b, s, h, d, causal, kv):
    lowers_for_tpu(
        jax.grad(lambda q, k, v: _sq(fa.flash_attention(q, k, v, causal)),
                 argnums=(0, 1, 2)), *_qkv(b, s, h, d, kv))


@pytest.mark.parametrize("b,s,h,d,causal,kv", FLASH_SHAPES[:4])
def test_flash_with_lse_grad_lowers(b, s, h, d, causal, kv):
    """Both outputs carry a cotangent — what ring attention differentiates."""

    def loss(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal)
        return _sq(out) + lse.sum()

    lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(b, s, h, d, kv))


@pytest.mark.parametrize("shape,kv,masking,strips_pairs", [
    ((2, 8192, 32, 64), 8, "causal", {128: 34078720, 256: 34603008}),
    ((1, 16384, 32, 128), 4, "block_diffusion",
     {128: 69206016, 256: 71303168})],
    ids=["the_causal_cells_layer", "the_block_diffusion_cells_layer"])
def test_each_flash_call_carries_its_score_pairs(shape, kv, masking,
                                                 strips_pairs):
    """The three launches of each decoder cell's attention at the cell's
    size and 1,024 x 1,024 blocks, lowered for the TPU: every call's
    ``kernel_metadata`` says the score pairs its live strips compute (a
    head's interior tiles whole and its diagonal tiles' live strips) and
    those the mask allows — one head's ``tile_pairs`` times the batch's
    query heads, whichever heads the launch's grid runs over — beside its
    grid steps, one head's table times the heads the grid runs over."""
    b, s, h, d = shape
    mask = fa.BlockDiffusionMask(s // 2, 4) if masking != "causal" else None
    grad = jax.grad(lambda q, k, v: _sq(fa.flash_attention(
        q, k, v, mask is None, 1024, 1024, mask)), argnums=(0, 1, 2))
    text = export.export(jax.jit(grad), platforms=("tpu",))(
        *_qkv(b, s, h, d, kv)).mlir_module()
    said = {name: dict(re.findall(r"\\22(\w+)\\22:\\22(\w*)\\22", meta))
            for name, meta in re.findall(
                r'kernel_name = "(_flash_\w+_kernel)".*?kernel_metadata = '
                r'"([^"]*)"', text)}
    assert set(said) == {"_flash_fwd_kernel", "_flash_bwd_dq_kernel",
                         "_flash_bwd_dkv_kernel"}
    n = s // 1024
    computed, allowed = fa.tile_pairs(n, n, 1024, 1024,
                                      mask or fa.CausalMask(0), None)
    assert computed == strips_pairs[fa.STRIP]
    for name, metadata in said.items():
        assert metadata["pairs"] == str(computed * b * h), name
        assert metadata["allowed"] == str(allowed * b * h), name
        heads = b * kv if "dkv" in name else b * h
        steps = sum(int(metadata[kind]) for kind in fa.TILE_KINDS)
        assert steps == heads * len(fa.tile_table(
            n, n, 1024, 1024, mask or fa.CausalMask(0), None,
            "dkv" in name, h // kv if "dkv" in name else 1))


def test_held_experts_grouped_products_lower_at_the_published_widths():
    """The dropless expert layer's first window and its second under the
    ``cond``, forward and backward, at 16,384 tokens, 8 of 32 experts
    held, hidden 2048, expert width 1792: grouped products, no pallas
    kernel of ours."""
    from analytics_zoo_tpu.ops import moe

    n, hidden, width, held, experts, k = 16384, 2048, 1792, 8, 32, 4

    def loss(x, w1, w3, w2, logits):
        ids, weights = moe.sigmoid_top_k_routing(
            logits, jnp.zeros((experts,)), k)
        out, _, _ = moe.held_expert_ffn(x, ids, weights, w1, w3, w2,
                                        tuple(range(held)), experts)
        return _sq(out)

    exported = export.export(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))),
        platforms=("tpu",))(
        S((n, hidden), jnp.bfloat16), S((held, hidden, width), jnp.float32),
        S((held, hidden, width), jnp.float32),
        S((held, width, hidden), jnp.float32), S((n, experts), jnp.float32))
    text = exported.mlir_module()
    assert "ragged_dot" in text
    assert "stablehlo.case" in text or "stablehlo.if" in text


@pytest.mark.parametrize("hidden,width,held,experts,k", [
    (2048, 768, 16, 128, 8), (2048, 1792, 8, 32, 4)],
    ids=["sdar_widths", "lfm2_widths"])
def test_held_expert_ffn_combines_through_the_kernels(monkeypatch, hidden,
                                                      width, held, experts,
                                                      k):
    """The dropless expert layer at each decoder cell's widths over 16,384
    tokens, forward and gradient, on a TPU: the combine (``_put_rows``'
    forward, ``_take_rows``' backward) lowers to the pack and sum kernels
    of ``ops/moe_combine.py``: a launch of each for each window forward,
    and as many again backward (the second window's under the ``cond``)."""
    from analytics_zoo_tpu.ops import moe

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    n = 16384

    def layer(x, w1, w3, w2, logits):
        ids, weights = moe.sigmoid_top_k_routing(
            logits, jnp.zeros((experts,)), k)
        return moe.held_expert_ffn(x, ids, weights, w1, w3, w2,
                                   tuple(range(held)), experts)[0]

    avals = (S((n, hidden), jnp.bfloat16), S((held, hidden, width),
                                             jnp.float32),
             S((held, hidden, width), jnp.float32),
             S((held, width, hidden), jnp.float32),
             S((n, experts), jnp.float32))
    for fn, launches in ((layer, 2), (jax.grad(
            lambda *a: _sq(layer(*a)), argnums=(0, 1, 2, 3)), 4)):
        exported = export.export(jax.jit(fn), platforms=("tpu",))(*avals)
        bodies = [base64.b64decode(body) for body in re.findall(
            r'tpu_custom_call.*?\\22body\\22: \\22([A-Za-z0-9+/=]*)',
            exported.mlir_module())]
        assert len(bodies) == 2 * launches
        for kernel in (b"_pack_rows_kernel", b"_sum_rows_kernel"):
            assert sum(kernel in body for body in bodies) == launches


def test_flash_small_block_q_is_widened_to_the_lane():
    """A caller's block_q below 128 that does not cover the sequence is
    rounded up: the backward's per-row statistics are [1, block_q] rows."""
    q = S((1, 512, 2, 128), jnp.bfloat16)
    lowers_for_tpu(
        jax.grad(lambda q, k, v: _sq(fa.flash_attention(
            q, k, v, False, 64, 64)), argnums=(0, 1, 2)), q, q, q)


NORM_ROTARY_SHAPES = [
    pytest.param(2, 2048, 8, id="b2s2048h8d128"),
    # the block-diffusion cell's q and k over its 16,384 rows
    pytest.param(1, 16384, 32, id="q-b1s16384h32d128"),
    pytest.param(1, 16384, 4, id="k-b1s16384h4d128"),
    # a last block that runs past the sequence
    pytest.param(1, 200, 2, id="ragged-s200"),
]


@pytest.mark.parametrize("b,s,h", NORM_ROTARY_SHAPES)
def test_norm_rotary_forward_and_grad_lower(monkeypatch, b, s, h):
    if s == 200:                                 # blocks of 128 rows
        monkeypatch.setattr(nr, "BLOCK_BYTES", 128 * h * 128 * 2)

    def loss(x, scale, table):
        return _sq(nr.norm_rotary(x, scale, table, h, 1e-6))

    avals = (S((b, s, h * 128), jnp.bfloat16), S((128,), jnp.float32),
             S((s, 128), jnp.float32))
    lowers_for_tpu(lambda x, scale, table: nr.norm_rotary(
        x, scale, table, h, 1e-6), *avals)
    lowers_for_tpu(jax.grad(loss, argnums=(0, 1)), *avals)


NCF_TABLES = (S((6041, 20), jnp.float32), S((3707, 20), jnp.float32))


@pytest.mark.parametrize("combine", ["concat", "sum", "mean", "mul"])
def test_fused_lookup_lowers_at_ncf_tables(combine):
    ids = S((8000, 2), jnp.int32)
    lowers_for_tpu(lambda ts, i: eb.fused_embedding_lookup(
        ts, i, combine, use_kernel=True), NCF_TABLES, ids)


def test_fused_lookup_value_and_grad_lowers():
    """The training step's use: the kernel forward under its custom VJP
    (the backward alone is a pure-jax scatter-add)."""
    ids = S((8000, 2), jnp.int32)
    lowers_for_tpu(
        jax.value_and_grad(lambda ts, i: eb.fused_embedding_lookup(
            ts, i, "concat", use_kernel=True).sum()), NCF_TABLES, ids)


@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag_lowers(mode):
    lowers_for_tpu(
        lambda t, i, n: eb.embedding_bag(t, i, n, mode, use_kernel=True),
        S((1000, 128), jnp.float32), S((256, 8), jnp.int32),
        S((256,), jnp.int32))


PAGES = [pytest.param(jnp.float32, 16, 128, id="f32-p16-d128"),
         pytest.param(jnp.int8, 16, 128, id="int8-p16-d128"),
         pytest.param(jnp.float32, 8, 64, id="f32-p8-d64"),
         pytest.param(jnp.int8, 32, 128, id="int8-p32-d128")]


def _page_avals(dtype, page_size, dim, batch=4, width=8, n_pages=64):
    return (S((n_pages, page_size, dim), dtype), S((batch, width), jnp.int32),
            S((batch,), jnp.int32), S((n_pages,), jnp.float32))


@pytest.mark.parametrize("dtype,page_size,dim", PAGES)
def test_paged_gather_lowers(dtype, page_size, dim):
    lowers_for_tpu(
        lambda p, t, n, s: pa.paged_gather_pinned(p, t, n, s,
                                                  use_kernel=True),
        *_page_avals(dtype, page_size, dim))


@pytest.mark.parametrize("dtype,page_size,dim", PAGES)
def test_paged_attention_lowers(dtype, page_size, dim):
    pool, table, lengths, scales = _page_avals(dtype, page_size, dim)
    lowers_for_tpu(
        lambda q, kp, vp, t, n, ks, vs: pa.paged_attention(
            q, kp, vp, t, n, k_scales=ks, v_scales=vs, use_kernel=True),
        S((table.shape[0], dim), jnp.float32), pool, pool, table, lengths,
        scales, scales)


def test_every_pallas_call_in_ops_is_covered():
    """A new kernel must join this file: count the call sites."""
    import os

    ops_dir = os.path.dirname(fa.__file__)
    sites = {}
    for name in sorted(os.listdir(ops_dir)):
        if name.endswith(".py"):
            with open(os.path.join(ops_dir, name)) as fh:
                n = len(re.findall(r"pl\.pallas_call\(", fh.read()))
            if n:
                sites[name] = n
    assert sites == {"embedding_bag.py": 2, "flash_attention.py": 1,
                     "moe_combine.py": 2, "norm_rotary.py": 2,
                     "paged_attention.py": 2}, sites
    # flash attention's one site is ``_tile_call``, which the forward,
    # ``dq`` and ``dk/dv`` launches go through
    with open(fa.__file__) as fh:
        assert len(re.findall(r"(?<!def )_tile_call\(", fh.read())) == 3
