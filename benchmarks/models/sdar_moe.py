"""Builds the system's own block-diffusion module for a configuration
file of the family ``sdar_moe``.

This is the one place that knows the system's names for the
configuration's keys. The parameter tree it expects is the one
``benchmarks/references/sdar_moe.param_shapes`` describes (checked by the
family's tests against ``module.init``'s shapes).

Brings ``build_module``, ``LOSS``, ``make_inputs``, ``TINY`` and
``FROZEN_LEAF`` (PERF.md, "adding a cell").
"""

import jax.numpy as jnp


def build_module(cfg: dict):
    from analytics_zoo_tpu.text.block_diffusion import BlockDiffusionLM
    from analytics_zoo_tpu.text.hybrid_decoder import HybridDecoderConfig
    if cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1 \
            or cfg["use_sliding_window"] or cfg["hidden_act"] != "silu":
        raise ValueError("a bias, tied embeddings, dense layers, a window "
                         "or another activation is not run")
    decoder = HybridDecoderConfig(
        vocab=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=("full_attention",) * cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_dense_layers=0, num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=tuple(cfg["held_experts"]),
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        initializer_range=cfg["initializer_range"],
        dtype={"bfloat16": jnp.bfloat16, "float32": None}[
            cfg["compute_dtype"]],
        head_size=cfg["head_dim"], router_scoring="softmax",
        tie_embeddings=False)
    return BlockDiffusionLM(decoder, block=cfg["block_length"],
                            mask_id=cfg["mask_token_id"],
                            eps=cfg["noise_eps"])


LOSS = "weighted_sparse_categorical_crossentropy_logits"

#: the configuration's keys at the size the CPU tests hold
#: (``benchmarks/harness/tiny.py``): two layers of hidden 32, heads of 16
#: (not hidden / heads), 2 of 8 experts held, 2 experts a token, blocks
#: of 4 positions
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=64, moe_intermediate_size=24,
            num_hidden_layers=2, router_experts=8, num_experts=2,
            held_experts=[0, 1], num_experts_per_tok=2, vocab_size=96,
            mask_token_id=95, block_length=4, calibration_batch=8,
            calibration_seq_len=16, router_tolerance=1.0)

#: path into the parameter tree of the leaf the faults test leaves unmoved:
#: one whose reference gradient is not among the leaves the comparison
#: leaves out and which is no smaller than the median leaf (the worst-leaf
#: measure reads an unmoved leaf as its change over the median leaf's)
FROZEN_LEAF = ("decoder", "block_1", "moe", "experts", "w1")


def make_inputs(cfg: dict, traffic: dict, rng, n: int):
    """``n`` rows of the traffic's length of token ids uniform over the
    vocabulary slice below the mask id, and the same ids as the labels
    (each noisy position is trained for its own clean id), from a numpy
    generator: every row differs."""
    import numpy as np
    ids = rng.integers(0, cfg["mask_token_id"],
                       (n, int(traffic["seq_len"])), dtype=np.int32)
    return ids, ids
