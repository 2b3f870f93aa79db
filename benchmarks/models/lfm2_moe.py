"""Builds the system's own hybrid decoder for a configuration file of the
family ``lfm2_moe``.

This is the one place that knows the system's names for the
configuration's keys. The parameter tree it expects is the one
``benchmarks/references/lfm2_moe.param_shapes`` describes (checked by the
family's tests against ``module.init``'s shapes).

Brings ``build_module``, ``LOSS``, ``make_inputs``, ``TINY`` and
``FROZEN_LEAF`` (PERF.md, "adding a cell").
"""

import jax.numpy as jnp


def build_module(cfg: dict):
    from analytics_zoo_tpu.text.hybrid_decoder import (HybridDecoder,
                                                       HybridDecoderConfig)
    if cfg["conv_bias"] or not cfg["use_expert_bias"]:
        raise ValueError("conv_bias true / use_expert_bias false is not run")
    return HybridDecoder(HybridDecoderConfig(
        vocab=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=tuple(cfg["held_experts"]),
        conv_taps=cfg["conv_L_cache"], norm_eps=cfg["norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        initializer_range=cfg["initializer_range"],
        dtype={"bfloat16": jnp.bfloat16, "float32": None}[
            cfg["compute_dtype"]]))


LOSS = "sparse_categorical_crossentropy_logits"

#: the configuration's keys at the size the CPU tests hold
#: (``benchmarks/harness/tiny.py``): three layers of hidden 32 (a dense
#: convolution layer, a sparse attention layer, a sparse convolution
#: layer), 2 of 8 experts held, 2 experts a token
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=64, moe_intermediate_size=24,
            num_hidden_layers=3,
            layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1, router_experts=8, num_experts=2,
            held_experts=[0, 1], num_experts_per_tok=2, vocab_size=96,
            calibration_batch=8, calibration_seq_len=16,
            expert_bias_tolerance=0.25)

#: path into the parameter tree of the leaf the faults test leaves unmoved:
#: one whose reference gradient is not among the leaves the comparison
#: leaves out (under a thousandth of the median leaf's) and which is no
#: smaller than the median leaf: Adam moves every element by the learning
#: rate, so a leaf's change is as large as its size, and the worst-leaf
#: measure reads an unmoved leaf as its change over the median leaf's
FROZEN_LEAF = ("block_1", "moe", "experts", "w1")


def make_inputs(cfg: dict, traffic: dict, rng, n: int):
    """``n`` rows of the traffic's length of token ids uniform over the
    vocabulary slice, and for every position its next id as the label,
    from a numpy generator: every row differs."""
    import numpy as np
    ids = rng.integers(0, cfg["vocab_size"],
                       (n, int(traffic["seq_len"]) + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]
