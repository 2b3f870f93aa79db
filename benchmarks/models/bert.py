"""Builds the system's own BERT module for a configuration file.

This is the one place that knows the system's names for a BERT
configuration's keys. The parameter tree it expects is the one
``benchmarks/references/bert.param_shapes`` describes (checked by the
rehearsal test against ``module.init``'s shapes).
"""

import flax.linen as nn
import jax.numpy as jnp

from analytics_zoo_tpu.text.bert import BertConfig, BertModule


def build_module(cfg: dict):
    """The classifier module: encoder + dense head on the pooled output
    (``chip_smoke._bert_classifier``'s module, from a config file)."""
    if cfg["hidden_act"] != "gelu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r} is not run")
    bert_cfg = BertConfig(
        vocab=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_block=cfg["num_hidden_layers"], n_head=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        hidden_drop=cfg["hidden_dropout_prob"],
        attn_drop=cfg["attention_probs_dropout_prob"],
        max_position_len=cfg["max_position_embeddings"],
        type_vocab=cfg["type_vocab_size"],
        initializer_range=cfg["initializer_range"], gelu_exact=True,
        dtype={"bfloat16": jnp.bfloat16, "float32": None}[
            cfg["compute_dtype"]])
    n_classes = cfg["n_classes"]

    class Classifier(nn.Module):
        @nn.compact
        def __call__(self, ids, train: bool = False):
            _, pooled = BertModule(bert_cfg, name="bert")(ids, train=train)
            return nn.Dense(n_classes)(pooled)

    return Classifier()


LOSS = "sparse_categorical_crossentropy_logits"


def make_inputs(cfg: dict, traffic: dict, rng, n: int):
    """``n`` rows of token ids of the traffic's length and a label each,
    from a numpy generator: every row differs."""
    import numpy as np
    ids = rng.integers(0, cfg["vocab_size"], (n, int(traffic["seq_len"])),
                       dtype=np.int32)
    labels = rng.integers(0, cfg["n_classes"], n, dtype=np.int32)
    return ids, labels
