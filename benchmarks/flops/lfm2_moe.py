"""Operations and bytes the mathematics of the ``lfm2_moe`` decoder needs
at this chip's expected share, from the shapes of a configuration file
alone. Counts what the cut model computes — a multiply-add is two
operations; causal attention forms half of the score matrix; a token
meets ``num_experts_per_tok * held / router_experts`` of the held experts
on average — never what an implementation executes: recomputation,
padding, a window wider than the expected rows and a kernel's internals do
not appear, so a share of peak built on these cannot pass 100% by
construction.

Imports nothing of the system under test.
"""

PASSES = {"train": 3, "serve": 1}


def _held(cfg: dict) -> int:
    return len(cfg["held_experts"])


def _layers(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in cfg["layer_types"])


def _sparse_layers(cfg: dict) -> int:
    return len(cfg["layer_types"]) - cfg["num_dense_layers"]


def expert_rows_per_token(cfg: dict) -> float:
    """Assignments that fall on held experts, per token, expected."""
    return cfg["num_experts_per_tok"] * _held(cfg) / cfg["router_experts"]


def attention_layer_forward_flops(cfg: dict, seq: int) -> int:
    """One attention layer for one sequence: q, k, v and output
    projections, and the causal half of scores and weighted values."""
    H = cfg["hidden_size"]
    kv = H // cfg["num_attention_heads"] * cfg["num_key_value_heads"]
    return 2 * seq * (2 * H * H + 2 * H * kv) + 2 * seq * seq * H


def expert_flops_per_row(cfg: dict) -> int:
    """One token through one gated expert."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops(cfg: dict, seq: int) -> float:
    """One sequence through the cut stack and the head."""
    H, I = cfg["hidden_size"], cfg["intermediate_size"]
    conv = 2 * H * 3 * H + 2 * H * H + 2 * cfg["conv_L_cache"] * H
    per_token = (_layers(cfg, "conv") * conv
                 + cfg["num_dense_layers"] * 6 * H * I
                 + _sparse_layers(cfg) * (
                     2 * H * cfg["router_experts"]
                     + expert_rows_per_token(cfg) * expert_flops_per_row(cfg))
                 + 2 * H * cfg["vocab_size"])
    return seq * per_token + _layers(cfg, "full_attention") \
        * attention_layer_forward_flops(cfg, seq)


def sample_flops(cfg: dict, traffic: dict, mode: str) -> float:
    """One sample (a row of the traffic's length): forward and backward
    (the backward of a matrix product is two products) in 'train'."""
    return forward_flops(cfg, int(traffic["seq_len"])) * PASSES[mode]


def attention_block_needs(cfg: dict, traffic: dict, rows: int, mode: str,
                          act_bytes: int = 2) -> dict:
    """Operations and least bytes of ALL attention layers for ``rows``
    sequences (causal, grouped heads). Bytes: each pass reads the block's
    input and writes its output once, in the 16-bit compute type; the
    weights, read once a step whatever the batch, are left out (the byte
    bound a little low: the safe side)."""
    seq, layers = int(traffic["seq_len"]), _layers(cfg, "full_attention")
    flops = PASSES[mode] * layers * rows \
        * attention_layer_forward_flops(cfg, seq)
    per_pass = 2 * rows * seq * cfg["hidden_size"] * act_bytes
    return {"flops": flops, "bytes": PASSES[mode] * layers * per_pass}


def moe_experts_needs(cfg: dict, traffic: dict, rows: int, mode: str,
                      act_bytes: int = 2) -> dict:
    """Operations and least bytes of the held experts' products of ALL
    sparse layers for ``rows`` sequences at the expected share of the
    assignments. Bytes: each pass reads the held experts' weights once a
    step and reads and writes each routed row once, all in the 16-bit
    compute type."""
    seq, layers = int(traffic["seq_len"]), _sparse_layers(cfg)
    routed = rows * seq * expert_rows_per_token(cfg)
    steps = rows / int(traffic["batch_size"])
    weights = _held(cfg) * 3 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"] * act_bytes
    per_pass = steps * weights + 2 * routed * cfg["hidden_size"] * act_bytes
    return {"flops": PASSES[mode] * layers * routed
            * expert_flops_per_row(cfg),
            "bytes": PASSES[mode] * layers * per_pass}
