"""Block-diffusion training of a decoder (BD3-LM, arXiv:2503.09573, with
the masked-diffusion weights of MDLM): a sequence of ``L`` ids in ``K = L
/ block`` blocks is corrupted block by block and the decoder learns to
restore the masked ids of a block from that block's noisy copy and the
CLEAN text before it. From the step's key:

    u[b, K] ~ U[0, 1);  t = (1 - eps) u + eps          one t a block a row
    m[b, L] ~ Bernoulli(t of the position's block);  xt = where(m, M, x0)
    input ids [xt ; x0]  (2L rows a sequence), positions [0..L-1 ; 0..L-1]
    attention under ``flash_attention.BlockDiffusionMask(L, block)``
    logits over the NOISY half, at each position for its own id (no shift)
    loss = sum m / t * nll(logits, x0) / (b * L)

so one pass of the stack over ``2L`` rows trains every block at once: the
clean half is computed only to be keys and values. ``BlockDiffusionLM``
is the module ``Estimator.from_flax`` takes, with the loss
``"weighted_sparse_categorical_crossentropy_logits"`` (learn/losses.py)
and the ids themselves as labels.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.flash_attention import BlockDiffusionMask
from analytics_zoo_tpu.ops.moe import sow_last
from analytics_zoo_tpu.text.hybrid_decoder import (HybridDecoder,
                                                   HybridDecoderConfig)


def corrupt(ids, key, block: int, mask_id: int, eps: float = 1e-3):
    """``ids`` [b, L] -> ``(xt [b, L], m [b, L] bool, t [b, L / block])``:
    each block of each row draws its own masking rate ``t`` in ``[eps,
    1)`` and each of its positions is replaced by ``mask_id`` with that
    probability."""
    with jax.named_scope("corrupt"):
        b, seq = ids.shape
        key_t, key_m = jax.random.split(key)
        u = jax.random.uniform(key_t, (b, seq // block), jnp.float32)
        t = (1.0 - eps) * u + eps
        m = jax.random.uniform(key_m, (b, seq), jnp.float32) \
            < jnp.repeat(t, block, axis=1)
        return jnp.where(m, jnp.int32(mask_id), ids), m, t


def _declare_step_metrics() -> None:
    """The series a block-diffusion step sows, under its help text
    (``telemetry.publish_step_counters`` fills it)."""
    from analytics_zoo_tpu.common import telemetry
    telemetry.get_registry().counter(
        "zoo_diffusion_positions_total",
        "Positions of the sequences a block-diffusion step trained on, "
        "per optimizer step: masked=true those the corruption replaced "
        "by the mask id (the ones the loss is taken over)",
        ("layer", "masked"))


class BlockDiffusionLM(nn.Module):
    """``ids`` [batch, L] -> in training ``(logits [batch, L, vocab] of
    the noisy half, weights [batch, L] float32)`` with ``weights = m /
    t``, the noise drawn from ``make_rng("dropout")`` (the Estimator's
    key of the step); with ``train=False`` the logits of the clean text
    alone under the mask's clean part (attention both ways inside a
    block, causal across blocks), so that ``predict`` and ``evaluate``
    get one array.

    Sows ``zoo_diffusion_positions_total{masked=true|false}`` into the
    ``counters`` collection each training step."""

    config: HybridDecoderConfig
    block: int
    mask_id: int
    eps: float = 1e-3

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        ids = jnp.asarray(input_ids).astype(jnp.int32)
        seq = ids.shape[1]
        decoder = HybridDecoder(self.config, name="decoder")
        if not train:
            return decoder(ids, mask=BlockDiffusionMask(
                seq, self.block, noisy=False))
        xt, m, t = corrupt(ids, self.make_rng("dropout"), self.block,
                           self.mask_id, self.eps)
        _declare_step_metrics()
        n_masked = jnp.sum(m, dtype=jnp.int32)
        sow_last(self, "zoo_diffusion_positions_total{masked=true}",
                 n_masked)
        sow_last(self, "zoo_diffusion_positions_total{masked=false}",
                 m.size - n_masked)
        logits = decoder(
            jnp.concatenate([xt, ids], axis=1),
            positions=np.tile(np.arange(seq, dtype=np.int32), 2),
            mask=BlockDiffusionMask(seq, self.block), head_rows=(0, seq))
        weights = m.astype(jnp.float32) / jnp.repeat(t, self.block, axis=1)
        return logits, weights
