"""Decoder-only language model whose layers are of two kinds by a
per-layer list: a gated short convolution (``ops/short_conv.py``) or
grouped-query attention with rotary positions (``ops/attention.py``), each
followed by a gated (SwiGLU) feed-forward block that is dense in the first
``num_dense_layers`` layers and a dropless mixture of experts
(``ops/moe.DroplessMoE``) in the rest. Pre-norm with RMSNorm, no learned
positions, no bias, no dropout; the head is the embedding transposed
(``tie_embeddings``, the default) or a matrix of its own:

    x = embed[ids]
    h = x + op(RMSNorm(x));  x = h + ffn(RMSNorm(h))      per layer
    logits = RMSNorm(x) @ embed.T          or  RMSNorm(x) @ lm_head

The stack is the hybrid family of ``transformers``' ``lfm2_moe`` and,
with attention layers only, a softmax router and an untied head, that of
``qwen3_moe`` / ``sdar_moe``; a layer is told which experts it holds
(``held_experts``), so that one chip of several that share each layer
runs its share through the same module. The attention layers are causal
over positions 0..seq-1 unless the call hands down a static ``mask`` and
``positions`` (``text/block_diffusion.py`` does).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import GroupedQueryAttention
from analytics_zoo_tpu.ops.flash_attention import RESIDUAL_NAMES
from analytics_zoo_tpu.ops.moe import DroplessMoE
from analytics_zoo_tpu.ops.short_conv import GatedShortConv

LAYER_TYPES = ("conv", "full_attention")


@dataclass(frozen=True)
class HybridDecoderConfig:
    vocab: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    moe_intermediate_size: int
    num_dense_layers: int
    num_experts: int                # the router's width
    num_experts_per_tok: int
    # ids of the experts each sparse layer holds; None: all of them
    held_experts: Optional[Tuple[int, ...]] = None
    conv_taps: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    initializer_range: float = 0.02
    # computation dtype (parameters stay float32)
    dtype: Optional[object] = None
    # a head's width where it is not hidden_size / num_heads
    head_size: Optional[int] = None
    # ops/moe.ROUTER_SCORINGS
    router_scoring: str = "sigmoid_bias"
    # the head is the embedding transposed; else a matrix ``lm_head``
    tie_embeddings: bool = True

    @property
    def head_dim(self) -> int:
        if self.head_size is not None:
            return self.head_size
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads


def _products_saveable(prim, *_, **__) -> bool:
    """The results of a layer's matrix products (grouped ones too)."""
    return prim.name in ("dot_general", "ragged_dot_general")


#: What a layer keeps for the backward pass beside its input: its
#: products' results, and the attention kernel's output and logsumexp —
#: 69 MB a layer at 2 x 8,192 x 32 x 64 (67 bf16 + 2 float32) and 136 MB
#: at 1 x 16,384 x 32 x 128 (134 + 2; the output is kept as
#: [batch, seq, heads, d] whichever layout the kernels read, the
#: logsumexp as [batch·heads, seq]), against a
#: second launch of the forward kernel in the backward pass. The rest is
#: recomputed (norms, gates, rotary positions, the sort and gathers of
#: the expert layer): a third of the activations' memory, which at 16k
#: tokens a step is what fits the step on a chip.
_BLOCK_POLICY = jax.checkpoint_policies.save_from_both_policies(
    _products_saveable,
    jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES))


class GatedMLP(nn.Module):
    """``w2(silu(w1 x) * w3 x)``."""

    width: int
    dtype: Optional[object] = None
    kernel_init: nn.initializers.Initializer = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=self.kernel_init, name=name)
        gated = nn.silu(dense("w1", self.width)(x)) \
            * dense("w3", self.width)(x)
        return dense("w2", x.shape[-1])(gated)


class DecoderBlock(nn.Module):
    config: HybridDecoderConfig
    layer_type: str
    sparse: bool
    # the attention layer's static mask; None: causal
    mask: Optional[object] = None

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        init = nn.initializers.normal(cfg.initializer_range)

        def norm(name):
            return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                              name=name)

        y = norm("operator_norm")(x)
        if self.layer_type == "conv":
            y = GatedShortConv(cfg.conv_taps, cfg.dtype, init,
                               name="conv")(y)
        elif self.layer_type == "full_attention":
            y = GroupedQueryAttention(
                cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                cfg.rope_theta, cfg.norm_eps, dtype=cfg.dtype,
                kernel_init=init, name="attention")(y, positions, self.mask)
        else:
            raise ValueError(f"layer type {self.layer_type!r} is not one "
                             f"of {LAYER_TYPES}")
        h = x + y
        y = norm("ffn_norm")(h)
        if self.sparse:
            y = DroplessMoE(
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size, cfg.held_experts,
                cfg.norm_topk_prob, cfg.routed_scaling_factor,
                dtype=cfg.dtype, kernel_init=init,
                scoring=cfg.router_scoring, name="moe")(y)
        else:
            y = GatedMLP(cfg.intermediate_size, cfg.dtype, init,
                         name="mlp")(y)
        return h + y


class HybridDecoder(nn.Module):
    """``ids`` [batch, seq] -> logits [batch, seq, vocab]. ``positions``
    [seq] and ``mask`` (a static ``flash_attention.TileMask``) go to
    every attention layer in place of 0..seq-1 and the causal mask;
    ``head_rows`` ``(start, stop)``: the rows of the sequence the head is
    applied to (logits [batch, stop - start, vocab])."""

    config: HybridDecoderConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = False, positions=None,
                 mask=None, head_rows=None):
        cfg = self.config
        ids = jnp.asarray(input_ids).astype(jnp.int32)
        embed = nn.Embed(cfg.vocab, cfg.hidden_size, dtype=cfg.dtype,
                         embedding_init=nn.initializers.normal(
                             cfg.initializer_range), name="embed")
        x = embed(ids)
        block_cls = nn.remat(DecoderBlock, policy=_BLOCK_POLICY)
        for i, layer_type in enumerate(cfg.layer_types):
            x = block_cls(cfg, layer_type, i >= cfg.num_dense_layers, mask,
                          name=f"block_{i}")(x, positions)
        if head_rows is not None:
            x = x[:, head_rows[0]:head_rows[1]]
        x = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                       name="out_norm")(x)
        # in the compute dtype: the loss takes its float32 copy a block
        # of positions at a time (learn/losses.py)
        if not cfg.tie_embeddings:
            return nn.Dense(cfg.vocab, use_bias=False, dtype=cfg.dtype,
                            kernel_init=nn.initializers.normal(
                                cfg.initializer_range), name="lm_head")(x)
        with jax.named_scope("lm_head"):
            return embed.attend(x)
