"""Transformer / BERT encoders as flax modules.

Parity targets: ``zoo/.../keras/layers/TransformerLayer.scala:56`` (GPT-2
style decoder stack: token+position embeddings, causal blocks) and
``BERT.scala:66`` (token/segment/position embeddings, bidirectional encoder
blocks, pooled [CLS] output) plus the python mirror
``pyzoo/zoo/pipeline/api/keras/layers/self_attention.py``. The reference
builds these from ~400 lines of BigDL graph plumbing per layer; here each
is a compact flax module over the fused attention op
(ops/attention.py → pallas flash kernel for long sequences), so the whole
encoder fuses under jit and shards with the standard strategies (tp rules
below).

Weight-compatible layout with the reference's BERT (kernel shapes match
google-research/bert naming at the block level), so checkpoints can be
mapped across.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import AttentionModule
from analytics_zoo_tpu.ops.hold import Dropout, gelu_exact


@dataclass(frozen=True)
class BertConfig:
    """(ref BERT.scala:66 constructor params / bert config.json)."""

    vocab: int = 30522
    hidden_size: int = 768
    n_block: int = 12
    n_head: int = 12
    intermediate_size: int = 3072
    hidden_drop: float = 0.1
    attn_drop: float = 0.1
    max_position_len: int = 512
    type_vocab: int = 2
    initializer_range: float = 0.02
    # exact (erf) gelu — what HF-format BERT checkpoints were trained
    # with (text/hf_import.py); the tanh approximation would put a ~1e-3
    # floor under import parity
    gelu_exact: bool = True
    # computation dtype (params stay fp32); jnp.bfloat16 doubles MXU
    # throughput on TPU — the default for training at scale
    dtype: Optional[object] = None
    # rematerialize each encoder block in the backward pass
    # (jax.checkpoint, keeping matmul outputs): activation memory drops
    # from O(n_block·b·L·hidden) to O(b·L·hidden) at ~⅓ extra forward
    # FLOPs — for LONG sequences / big batches that otherwise don't fit
    # HBM. Off by default: when everything fits, remat only costs MFU.
    remat: bool = False
    # attention backend: None → ops/attention.py auto-select, True → the
    # tuned pallas path (ops/autotune.py auto_flash_attention — engages
    # the kernel only where a measurement beat blockwise; head_dim 64 is
    # covered via lane padding), False → reference einsum attention
    use_flash: Optional[bool] = None

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.n_head == 0
        return self.hidden_size // self.n_head


class EncoderBlock(nn.Module):
    """Post-LN transformer block (BERT ordering: attn → add&norm → ffn →
    add&norm; ref TransformerLayer.scala block / BERT.scala)."""

    hidden_size: int
    n_head: int
    intermediate_size: int
    dropout: float = 0.1
    attn_drop: float = 0.1
    causal: bool = False
    # computation dtype for the whole block INCLUDING the layernorms:
    # flax LayerNorm computes mean/var in fp32 internally regardless, so
    # dtype=bf16 only affects the normalized output — keeping the
    # residual stream bf16 instead of letting fp32 LN params promote it
    # (measured +0.06 MFU on BERT-base/v5e)
    dtype: Optional[object] = None
    # erf gelu for BERT-checkpoint fidelity (HF trained with exact);
    # the GPT-style causal stack keeps the canonical tanh approximation
    gelu_exact: bool = False
    # threaded to AttentionModule (see BertConfig.use_flash)
    use_flash: Optional[bool] = None

    @nn.compact
    def __call__(self, x, mask=None, train: bool = False):
        attn = AttentionModule(
            num_heads=self.n_head,
            head_dim=self.hidden_size // self.n_head,
            dropout=self.attn_drop, causal=self.causal, dtype=self.dtype,
            use_flash=self.use_flash,
            name="attention")(x, mask=mask, train=train)
        x = nn.LayerNorm(epsilon=1e-12, dtype=self.dtype,
                         name="attn_norm")(x + attn)
        h = nn.Dense(self.intermediate_size, dtype=self.dtype,
                     name="intermediate")(x)
        # the erfc of the exact gelu and every dropout mask are held
        # (ops/hold.py): computed once a step, not inside each product
        h = gelu_exact(h) if self.gelu_exact \
            else nn.gelu(h, approximate=True)
        h = nn.Dense(self.hidden_size, dtype=self.dtype, name="output")(h)
        if self.dropout > 0:
            h = Dropout(self.dropout, deterministic=not train)(h)
        return nn.LayerNorm(epsilon=1e-12, dtype=self.dtype,
                            name="ffn_norm")(x + h)


class BertModule(nn.Module):
    """BERT encoder (ref BERT.scala:66; outputs = (sequence, pooled) like
    the reference's ``outputAllBlock=false`` mode)."""

    config: BertConfig = BertConfig()

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 train: bool = False):
        cfg = self.config
        ids = jnp.asarray(input_ids).astype(jnp.int32)
        b, L = ids.shape
        if L > cfg.max_position_len:
            # XLA clamps out-of-range gathers, which would silently reuse
            # the last position embedding — fail loudly instead
            raise ValueError(f"sequence length {L} exceeds "
                             f"max_position_len {cfg.max_position_len}")
        emb = nn.Embed(cfg.vocab, cfg.hidden_size,
                       name="word_embeddings")(ids)
        pos = jnp.arange(L)[None, :]
        emb = emb + nn.Embed(cfg.max_position_len, cfg.hidden_size,
                             name="position_embeddings")(pos)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(ids)
        emb = emb + nn.Embed(cfg.type_vocab, cfg.hidden_size,
                             name="token_type_embeddings")(
            jnp.asarray(token_type_ids).astype(jnp.int32))
        x = nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype,
                         name="embed_norm")(emb)
        if cfg.hidden_drop > 0:
            x = Dropout(cfg.hidden_drop, deterministic=not train)(x)

        mask = None
        if attention_mask is not None:
            # [b, L] 1/0 → [b, 1, 1, L] broadcast over heads and queries
            mask = jnp.asarray(attention_mask)[:, None, None, :]
        block_cls = EncoderBlock
        if cfg.remat:
            # recompute block activations in backward; dot outputs with no
            # batch dims (weight-stationary matmul results) stay saved so
            # the recompute is elementwise+attention only
            block_cls = nn.remat(
                EncoderBlock, static_argnums=(3,),
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        for i in range(cfg.n_block):
            x = block_cls(
                hidden_size=cfg.hidden_size, n_head=cfg.n_head,
                intermediate_size=cfg.intermediate_size,
                dropout=cfg.hidden_drop, attn_drop=cfg.attn_drop,
                dtype=cfg.dtype, gelu_exact=cfg.gelu_exact,
                use_flash=cfg.use_flash,
                name=f"block_{i}")(x, mask, train)
        pooled = nn.tanh(nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                                  name="pooler")(x[:, 0]))
        return x, pooled


class TransformerModule(nn.Module):
    """GPT-style causal decoder stack (ref TransformerLayer.scala:56:
    token+position embeddings, causal self-attention blocks; returns the
    full sequence representation)."""

    vocab: int
    hidden_size: int = 768
    n_block: int = 12
    n_head: int = 12
    intermediate_size: Optional[int] = None
    hidden_drop: float = 0.1
    attn_drop: Optional[float] = None  # None → follow hidden_drop
    max_position_len: int = 512
    dtype: Optional[object] = None     # computation dtype (params fp32)

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        ids = jnp.asarray(input_ids).astype(jnp.int32)
        b, L = ids.shape
        if L > self.max_position_len:
            raise ValueError(f"sequence length {L} exceeds "
                             f"max_position_len {self.max_position_len}")
        x = nn.Embed(self.vocab, self.hidden_size, name="wte")(ids)
        x = x + nn.Embed(self.max_position_len, self.hidden_size,
                         name="wpe")(jnp.arange(L)[None, :])
        if self.hidden_drop > 0:
            x = Dropout(self.hidden_drop, deterministic=not train)(x)
        inter = self.intermediate_size or 4 * self.hidden_size
        attn_drop = (self.hidden_drop if self.attn_drop is None
                     else self.attn_drop)
        for i in range(self.n_block):
            x = EncoderBlock(
                hidden_size=self.hidden_size, n_head=self.n_head,
                intermediate_size=inter, dropout=self.hidden_drop,
                attn_drop=attn_drop, dtype=self.dtype,
                causal=True, name=f"block_{i}")(x, train=train)
        return x


def bert_tp_rules() -> list:
    """Tensor-parallel partition rules for the encoder: attention heads and
    FFN width shard over the ``model`` axis (Megatron layout: column-
    parallel QKV/intermediate, row-parallel out/output)."""
    return [
        (r"attention/(query|key|value)/kernel", (None, "model", None)),
        (r"attention/out/kernel", ("model", None, None)),
        (r"intermediate/kernel", (None, "model")),
        (r"output/kernel", ("model", None)),
        (r"word_embeddings/embedding", (None, "model")),
    ]
