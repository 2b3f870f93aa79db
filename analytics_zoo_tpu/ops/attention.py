"""Fused scaled-dot-product attention.

The reference's attention is plain BigDL matmul composition
(ref ``pyzoo/zoo/pipeline/api/keras/layers/self_attention.py`` 386 LoC,
``zoo/.../keras/layers/TransformerLayer.scala:56``). Here:

- default path: ``jax.nn.dot_product_attention``-style fused einsum chain —
  XLA fuses softmax into the MXU matmuls;
- TPU path: the pallas flash-attention kernel (``ops/flash_attention.py``)
  for long sequences — O(seq) memory via online softmax, dispatched when
  running on TPU and seq_len is tile-aligned;
- sequence-parallel path: ring attention over the ``seq`` mesh axis
  (``ops/ring_attention.py``).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype

from analytics_zoo_tpu.ops import norm_rotary
from analytics_zoo_tpu.ops.hold import Dropout, hold_both_ways


def dot_product_attention(q, k, v, mask=None, causal: bool = False,
                          use_flash: Optional[bool] = None):
    """q,k,v: [batch, seq, heads, head_dim] → [batch, seq, heads, head_dim];
    ``k`` and ``v`` may come at fewer heads, each serving the
    ``heads // kv_heads`` consecutive query heads of its group: the
    kernels read them so, and the dense path and the blockwise scan get
    them repeated (``flash_attention.repeat_kv_heads``).

    ``use_flash=None`` auto-selects the pallas path on TPU: a persisted
    autotuner verdict for the shape wins outright; without one, the HBM
    heuristic below decides. ``use_flash=True`` routes through
    ``ops.autotune.auto_flash_attention`` — the tuned block config when
    the measurement says the kernel beats blockwise, the blockwise
    reference otherwise — so forcing flash can never be slower than the
    fallback as measured.

    ``mask``: an array of allowed pairs, which always takes the dense
    path, or a STATIC mask (``flash_attention.TileMask``, given in place
    of ``causal``), which the kernels and the blockwise scan take as they
    take ``causal`` and the dense path takes as the array it stands for.
    """
    from analytics_zoo_tpu.ops.flash_attention import (TileMask,
                                                       repeat_kv_heads)
    static = mask if isinstance(mask, TileMask) else None
    if use_flash is None:
        use_flash = _flash_ok(q, k, mask)
    if use_flash and (mask is None or static is not None):
        from analytics_zoo_tpu.ops.autotune import auto_flash_attention
        return auto_flash_attention(q, k, v, causal=causal, mask=static)
    if static is not None:
        mask = static.dense(q.shape[1], k.shape[1])
    return _reference_attention(q, *repeat_kv_heads(q, k, v), mask=mask,
                                causal=causal)


def _flash_ok(q, k, mask) -> bool:
    """Use the pallas path only where it wins. A persisted autotune verdict
    for this exact shape is the ground truth; with no verdict yet, the
    structural heuristic: long sequences whose full [b,h,sq,sk] score
    matrix would blow HBM (measured on v5e: XLA's fused attention is
    faster up to ~4k seq; beyond that the O(s²) buffer dominates). The
    kernels pad internally now, so neither ragged seq lengths nor
    head_dim % 128 != 0 (the 64-dim BERT class) disqualify a shape. An
    ARRAY mask means the dense path (the kernels read no mask from
    memory); a static one goes by the heuristic alone (no verdict is
    kept for a masked shape)."""
    from analytics_zoo_tpu.ops.flash_attention import TileMask, on_tpu
    static = isinstance(mask, TileMask)
    if (mask is not None and not static) or not on_tpu():
        return False
    b, sq, h, d = q.shape
    sk = k.shape[1]
    from analytics_zoo_tpu.ops import autotune
    rec = None if static else autotune.get_tuner().lookup(
        autotune.attention_key(b, sq, sk, h, d, q.dtype, False),
        "flash_attention")
    if rec is not None:
        return bool(rec.get("use_kernel"))
    return 4 * b * h * sq * sk > autotune.SCORES_SWITCH  # > 2 GiB


def _reference_attention(q, k, v, mask=None, causal=False,
                         return_probs: bool = False):
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(cmask, scores, jnp.finfo(scores.dtype).min)
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores,
                           jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return (out, probs) if return_probs else out


class _ProjParams(nn.Module):
    """Holds one head-projection's parameters without computing anything.

    Shapes and initialization reproduce ``nn.DenseGeneral((heads, head_dim))``
    exactly (kernel initialized on the flattened (in, heads*head_dim) shape,
    then reshaped), so the param tree is bit-identical to the DenseGeneral
    formulation this replaced — HF checkpoint import (text/hf_import.py) and
    the TP partition rules (text/bert.py bert_tp_rules) key on these names.
    Keeping the three projections as separate params but computing them as
    ONE packed matmul is measurably faster on the MXU (one 768×2304 matmul
    beats three 768×768 at BERT shapes) without changing any checkpoint."""

    in_features: int
    heads: int
    head_dim: int

    @nn.compact
    def __call__(self):
        h, d = self.heads, self.head_dim

        def kernel_init(rng, *_):
            flat = nn.initializers.lecun_normal()(
                rng, (self.in_features, h * d), jnp.float32)
            return flat.reshape(self.in_features, h, d)

        kernel = self.param("kernel", kernel_init)
        bias = self.param("bias", nn.initializers.zeros_init(), (h, d),
                          jnp.float32)
        return kernel, bias


class AttentionModule(nn.Module):
    """Projection + fused attention + output projection.

    ``dtype``: computation dtype (params stay fp32) — bf16 doubles MXU
    throughput on TPU.

    ``self_attention``: force the packed-QKV path on (True) or off (False).
    The default (None) falls back to an *identity* check — packed when
    ``kv_in is None or kv_in is q_in`` — which catches callers that pass
    the same array twice (keras MultiHeadAttention does), but NOT callers
    whose arguments were rebound by a transform: ``jax.checkpoint`` /
    ``jax.vmap`` / donated buffers hand the module two *distinct* tracers
    for the same value, silently demoting it to three separate matmuls.
    Set ``self_attention=True`` when the module is constructed for a
    self-attention site to make the fused path transform-proof."""

    num_heads: int
    head_dim: int
    dropout: float = 0.0
    causal: bool = False
    dtype: Optional[jnp.dtype] = None
    self_attention: Optional[bool] = None
    # None → dot_product_attention's auto-select; True forces the tuned
    # pallas path (auto_flash_attention: kernel only where measured
    # faster); False pins the reference einsum chain
    use_flash: Optional[bool] = None

    @nn.compact
    def __call__(self, q_in, kv_in=None, mask=None, train: bool = False):
        # explicit flag wins; the identity-check fallback keeps old call
        # sites working but does not survive argument-rebinding transforms
        # (see class docstring)
        self_attn = (self.self_attention if self.self_attention is not None
                     else kv_in is None or kv_in is q_in)
        kv_in = q_in if kv_in is None else kv_in
        h, d = self.num_heads, self.head_dim
        wq, bq = _ProjParams(q_in.shape[-1], h, d, name="query")()
        wk, bk = _ProjParams(kv_in.shape[-1], h, d, name="key")()
        wv, bv = _ProjParams(kv_in.shape[-1], h, d, name="value")()
        if self_attn:
            # one packed (in, 3·h·d) matmul instead of three (in, h·d)
            w = jnp.concatenate(
                [p.reshape(p.shape[0], h * d) for p in (wq, wk, wv)], -1)
            b = jnp.concatenate(
                [p.reshape(h * d) for p in (bq, bk, bv)])
            x, w, b = promote_dtype(q_in, w, b, dtype=self.dtype)
            qkv = (x @ w + b).reshape(*x.shape[:-1], 3, h, d)
            q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        else:
            def proj(x, w, b):
                x, w, b = promote_dtype(x, w, b, dtype=self.dtype)
                return jnp.einsum("...i,ihd->...hd", x, w) + b
            q = proj(q_in, wq, bq)
            k = proj(kv_in, wk, bk)
            v = proj(kv_in, wv, bv)
        out = dot_product_attention(q, k, v, mask=mask, causal=self.causal,
                                    use_flash=self.use_flash)
        out = nn.DenseGeneral(q_in.shape[-1], axis=(-2, -1),
                              dtype=self.dtype, name="out")(out)
        if self.dropout > 0:
            out = Dropout(self.dropout, deterministic=not train)(out)
        return out


# ------------------------------------ grouped-query heads, rotary positions

def rotary_embedding(x, theta: float, positions=None):
    """Rotary positions on ``x`` [batch, seq, heads, head_dim] with the
    halves rotated (``rotate_half``: element i pairs with i + d/2), the
    angles in float32: ``x * cos + rotate_half(x) * sin``. ``positions``
    [seq]: each row's position (default 0..seq-1; they may repeat)."""
    d = x.shape[-1]
    table = norm_rotary.rotary_table(x.shape[1], d, theta, positions)
    cos = jnp.concatenate([table[:, :d // 2]] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([table[:, d // 2:]] * 2, -1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    rotated = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + rotated * sin).astype(x.dtype)


def grouped_query_attention(q, k, v, mask=None):
    """Attention of ``q`` [batch, seq, heads, d] over ``k``, ``v``
    [batch, seq, kv_heads, d] with ``heads`` a multiple of ``kv_heads``:
    each key-value head serves ``heads // kv_heads`` consecutive query
    heads. Causal, or under the static ``mask``. On the kernel path k and
    v go as they are: the kernels name a key/value head by its group."""
    return dot_product_attention(q, k, v, mask=mask, causal=mask is None)


class _NormScale(nn.Module):
    """An RMSNorm's ``scale`` [features] as ``nn.RMSNorm`` makes it (ones,
    float32), computing nothing: the parameter of a norm that
    ``ops/norm_rotary.py`` applies, under the norm's own name."""

    features: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.features,),
                          jnp.float32)


class GroupedQueryAttention(nn.Module):
    """Self-attention with grouped-query heads, an RMSNorm over each
    head of q and of k, rotary positions and no bias: projections ``q``,
    ``k``, ``v``, ``out``; norms ``q_norm``, ``k_norm``. Causal over
    positions 0..seq-1, or under a static ``mask``
    (``flash_attention.TileMask``) at the ``positions`` [seq] given.

    Where ``norm_rotary.engages`` (a TPU, a head of whole lanes: the
    flash kernels' ``rows`` layout) each norm and its rotation are one
    kernel each way on the projection's ``[batch, seq, heads·d]`` rows;
    elsewhere they are XLA's ``nn.RMSNorm`` and ``rotary_embedding``. The
    parameters are the same either way."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Optional[jnp.dtype] = None
    kernel_init: nn.initializers.Initializer = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x, positions=None, mask=None):
        b, s, hidden = x.shape
        h, g, d = self.num_heads, self.num_kv_heads, self.head_dim

        rows = norm_rotary.engages(d)

        def proj(name, heads):
            y = nn.Dense(heads * d, use_bias=False, dtype=self.dtype,
                         kernel_init=self.kernel_init, name=name)(x)
            return y if rows else y.reshape(b, s, heads, d)

        q, k, v = proj("q", h), proj("k", g), proj("v", g)
        if rows:
            # on the projections' own [batch, seq, heads·d] rows, which
            # the kernels read as they are: no float32 copy of q or k and
            # no relayout between (heads, d) tiles and rows
            table = norm_rotary.rotary_table(s, d, self.rope_theta,
                                             positions)
            q = norm_rotary.norm_rotary(
                q, _NormScale(d, name="q_norm")(), table, h, self.norm_eps)
            k = norm_rotary.norm_rotary(
                k, _NormScale(d, name="k_norm")(), table, g, self.norm_eps)
            q, k, v = (a.reshape(b, s, -1, d) for a in (q, k, v))
        else:
            q = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           name="q_norm")(q)
            k = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           name="k_norm")(k)
            q = rotary_embedding(q, self.rope_theta, positions)
            k = rotary_embedding(k, self.rope_theta, positions)
            # held at the kernels' door, value and cotangent: a relayout
            # the kernels need is made once, on the rounded value, not
            # hoisted onto the chain's float32 intermediates (ops/hold.py)
            q, k = hold_both_ways(q), hold_both_ways(k)
        out = grouped_query_attention(q, k, v, mask)
        return nn.Dense(hidden, use_bias=False, dtype=self.dtype,
                        kernel_init=self.kernel_init,
                        name="out")(out.reshape(b, s, h * d))


# ------------------------------------------------------ latent attention

def deinterleave(x):
    """The channels of ``x`` [..., d] in pairs ``(2i, 2i + 1)`` laid out
    as halves: ``[x0, x2, ..., x1, x3, ...]``, so that ``rotary_embedding``
    (``rotate_half``) rotates each pair, as ``transformers``'
    ``apply_rotary_pos_emb_interleave`` reorders them."""
    d = x.shape[-1]
    return x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(
        x.shape)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, no query compression)
    over the ``num_heads`` heads this layer holds, causal, with a
    head-wise output gate. Per token ``x``, per head:

        [q_nope | q_rope] = W_q x                       (qk_nope + qk_rope)
        [c | k_rope] = W_kv_a x;  c = RMSNorm(c)        (kv_lora_rank + qk_rope)
        [k_nope | v] = W_kv_b c                         (qk_nope + v_head)
        q, k = RMSNorm over each head's qk_nope + qk_rope, then rotary
               positions on the qk_rope channels, pairs interleaved
               (``deinterleave``); ``k_rope`` is one for all heads
        a = softmax_causal(q kᵀ / sqrt(qk_nope + qk_rope)) v
        y = W_o [a · σ(w_gᵀ x)]                         (a gate a head)

    The latent ``c`` serves every head, so ``W_kv_a`` is whole on every
    chip that holds some of the heads; the shares of a partition of the
    heads add up to the whole layer. Projections ``q``, ``kv_a``,
    ``kv_b``, ``gate``, ``o``; norms ``kv_a_norm``, ``q_norm``,
    ``k_norm``. The value head is narrower than the query/key head, and
    the flash kernels take it at its own width: on a TPU or under the
    interpreter the kernels at ``autotune.untuned_blocks`` (no verdict is
    looked up: the tuner times a value width equal to the key's only),
    the dense einsum chain elsewhere."""

    num_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Optional[jnp.dtype] = None
    kernel_init: nn.initializers.Initializer = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        h, dn, dr = self.num_heads, self.qk_nope_dim, self.qk_rope_dim
        dv, r = self.v_head_dim, self.kv_lora_rank
        if None in (dn, dr, dv, r):
            raise ValueError("latent attention needs qk_nope_dim, "
                             "qk_rope_dim, v_head_dim and kv_lora_rank")

        def dense(name, features, y):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=self.kernel_init, name=name)(y)

        def norm(name, y):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              name=name)(y)

        q = dense("q", h * (dn + dr), x).reshape(b, s, h, dn + dr)
        c, k_rope = jnp.split(dense("kv_a", r + dr, x), [r], axis=-1)
        kv = dense("kv_b", h * (dn + dv), norm("kv_a_norm", c)).reshape(
            b, s, h, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None, :],
                                            (b, s, h, dr))], -1)
        q, k = norm("q_norm", q), norm("k_norm", k)

        def rotate(y):
            return jnp.concatenate([y[..., :dn], rotary_embedding(
                deinterleave(y[..., dn:]), self.rope_theta)], -1)

        from analytics_zoo_tpu.ops import autotune
        from analytics_zoo_tpu.ops.flash_attention import (flash_attention,
                                                           on_tpu,
                                                           pallas_interpret)
        q, k, v = rotate(q), rotate(k), kv[..., dn:]
        if on_tpu() or pallas_interpret():
            out = flash_attention(q, k, v, True, *autotune.untuned_blocks(
                dn + dr, q.dtype))
        else:
            out = _reference_attention(q, k, v, causal=True)
        gate = jax.nn.sigmoid(dense("gate", h, x).astype(jnp.float32))
        out = (out * gate[..., None]).astype(out.dtype)
        return dense("o", hidden, out.reshape(b, s, h * dv))
