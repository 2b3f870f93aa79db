"""Builds the system's own hybrid decoder for a configuration file of the
family ``bailing_hybrid``: Kimi delta attention and latent attention
layers, grouped sigmoid routing with a shared expert, an untied head.

This is the one place that knows the system's names for the
configuration's keys. The parameter tree it expects is the one
``benchmarks/references/bailing_hybrid.param_shapes`` describes (checked
by the family's tests against ``module.init``'s shapes). It refuses the
configuration's switches the program does not implement, on the layers
it runs, rather than guess at them.

Brings ``build_module``, ``LOSS``, ``make_inputs``, ``TINY`` and
``FROZEN_LEAF`` (PERF.md, "adding a cell").
"""

import jax.numpy as jnp

#: switches whose true setting is a mechanism the program does not run
_NOT_RUN_WHEN_TRUE = ("use_kda_lora", "use_nGPT", "use_mla_nope",
                      "up_proj_norm", "value_norm", "use_bias",
                      "use_qkv_bias", "scale_router_input",
                      "tie_word_embeddings")
#: the settings the program runs, key by key
_RUN_AS = dict(hidden_act="silu", q_lora_rank=None, kda_safe_gate=True,
               linear_silu=True, no_kda_lora=True,
               num_kv_heads_for_linear_attn=0, group_norm_size=1,
               gated_attention_proj_granularity_type="head_wise",
               topk_method="noaux_tc", scoring_func="sigmoid",
               score_function="sigmoid", moe_router_enable_expert_bias=True,
               num_shared_experts=1, use_qk_norm=True, rope_interleave=True,
               kda_lower_bound=-5, num_nextn_predict_layers=0)


def layer_type(cfg: dict, published: int) -> str:
    """The kind of published layer ``published``: latent attention every
    ``layer_group_size``-th layer, Kimi delta attention otherwise."""
    group = int(cfg["layer_group_size"])
    return "latent_attention" if (published + 1) % group == 0 \
        else "linear_attention"


def check_runnable(cfg: dict) -> None:
    """Raise on a switch or a clamp the program does not run."""
    on = [k for k in _NOT_RUN_WHEN_TRUE if cfg[k]]
    off = {k: cfg[k] for k, v in _RUN_AS.items() if cfg[k] != v}
    if on or off:
        raise ValueError(f"not run: {on} set, {off} where the program runs "
                         f"{ {k: _RUN_AS[k] for k in off} }")
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        clamped = [i for i in cfg["published_layers"] if cfg[key][i]]
        if clamped:
            raise ValueError(f"not run: {key} clamps published layers "
                             f"{clamped}")
    kinds = [layer_type(cfg, i) for i in cfg["published_layers"]]
    if kinds != list(cfg["layer_types"]) \
            or len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types {cfg['layer_types']} are not those "
                         f"of published layers {cfg['published_layers']}")


def build_module(cfg: dict):
    from analytics_zoo_tpu.text.hybrid_decoder import (HybridDecoder,
                                                       HybridDecoderConfig)
    check_runnable(cfg)
    return HybridDecoder(HybridDecoderConfig(
        vocab=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=tuple(cfg["held_experts"]),
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        initializer_range=cfg["initializer_range"],
        dtype={"bfloat16": jnp.bfloat16, "float32": None}[
            cfg["compute_dtype"]],
        head_size=cfg["head_dim"], tie_embeddings=False, n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        shared_expert_size=cfg["num_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"],
        conv_taps=cfg["short_conv_kernel_size"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"]))


LOSS = "sparse_categorical_crossentropy_logits"

#: the configuration's keys at the size the CPU tests hold
#: (``benchmarks/harness/tiny.py``): three layers of hidden 32 (published
#: layers 5, 6 and 11: a dense latent attention layer, a sparse delta-rule
#: layer, a sparse latent attention layer), two heads of 16 (latent: 8 + 8
#: for q and k, 8 for v), 2 of 16 experts held in 4 groups of which 2 are
#: kept, 2 experts a token. (The leaves the comparison weighs, all but
#: the expert biases, are an odd number: the median leaf of a state left
#: unchanged then reads exactly 1.)
TINY = dict(hidden_size=32, num_attention_heads=2, head_dim=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, qk_head_dim=16,
            rotary_dim=8, v_head_dim=8, kv_lora_rank=16,
            intermediate_size=64, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=24, num_hidden_layers=3,
            published_layers=[5, 6, 11],
            layer_types=["latent_attention", "linear_attention",
                         "latent_attention"],
            first_k_dense_replace=1, router_experts=16, n_group=4,
            topk_group=2, num_experts=2, held_experts=[0, 1],
            num_experts_per_tok=2, vocab_size=96, calibration_batch=8,
            calibration_seq_len=16, expert_bias_tolerance=1.0)

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
