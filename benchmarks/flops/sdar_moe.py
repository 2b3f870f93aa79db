"""Operations and bytes the mathematics of ``sdar_moe`` block-diffusion
training needs at this chip's expected share, from the shapes of a
configuration file alone. A sample is one sequence of ``L`` ids: ``2L``
rows (its noisy and its clean copy) go through every layer and ``L``
through the head. Counts what the cut model computes — a multiply-add is
two operations; attention forms the ALLOWED pairs of the three-region
mask only, ``2 * sum_{i < L} B * (block(i) + 1) = L * (L + B)`` a
sequence, the noisy and the clean half alike; a row meets
``num_experts_per_tok * held / router_experts`` of the held experts on
average — never what an implementation executes: recomputation, padding,
masked halves of diagonal tiles, a window wider than the expected rows
and a kernel's internals do not appear, so a share of peak built on
these cannot pass 100% by construction.

Imports nothing of the system under test.
"""

PASSES = {"train": 3, "serve": 1}


def _held(cfg: dict) -> int:
    return len(cfg["held_experts"])


def allowed_pairs(cfg: dict, seq: int) -> int:
    """(query, key) pairs the mask allows in one sequence's ``2 * seq``
    rows: a row of block ``b``, noisy or clean, sees ``B * (b + 1)``
    keys."""
    B = int(cfg["block_length"])
    blocks = seq // B
    return B * B * blocks * (blocks + 1)      # 2 * sum_i B * (i // B + 1)


def expert_rows_per_token(cfg: dict) -> float:
    """Assignments that fall on held experts, per row, expected."""
    return cfg["num_experts_per_tok"] * _held(cfg) / cfg["router_experts"]


def attention_layer_forward_flops(cfg: dict, seq: int) -> int:
    """One attention layer for one sequence: q, k, v and output
    projections of its ``2 * seq`` rows, and scores and weighted values
    of the allowed pairs."""
    H = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * (2 * seq) * (2 * H * q + 2 * H * kv) \
        + 4 * allowed_pairs(cfg, seq) * q


def expert_flops_per_row(cfg: dict) -> int:
    """One row through one gated expert."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops(cfg: dict, seq: int) -> float:
    """One sequence through the cut stack and the head."""
    H, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    per_row = 2 * H * cfg["router_experts"] \
        + expert_rows_per_token(cfg) * expert_flops_per_row(cfg)
    return layers * (2 * seq * per_row
                     + attention_layer_forward_flops(cfg, seq)) \
        + seq * 2 * H * cfg["vocab_size"]


def sample_flops(cfg: dict, traffic: dict, mode: str) -> float:
    """One sample (a sequence of the traffic's length): forward and
    backward (the backward of a matrix product is two products) in
    'train'."""
    return forward_flops(cfg, int(traffic["seq_len"])) * PASSES[mode]


def attention_block_needs(cfg: dict, traffic: dict, rows: int, mode: str,
                          act_bytes: int = 2) -> dict:
    """Operations and least bytes of ALL attention layers for ``rows``
    sequences. Bytes: each pass reads the block's input and writes its
    output once, ``2 * seq`` rows a sequence, in the 16-bit compute type;
    the weights, read once a step whatever the batch, are left out (the
    byte bound a little low: the safe side)."""
    seq, layers = int(traffic["seq_len"]), cfg["num_hidden_layers"]
    flops = PASSES[mode] * layers * rows \
        * attention_layer_forward_flops(cfg, seq)
    per_pass = 2 * rows * 2 * seq * cfg["hidden_size"] * act_bytes
    return {"flops": flops, "bytes": PASSES[mode] * layers * per_pass}


def moe_experts_needs(cfg: dict, traffic: dict, rows: int, mode: str,
                      act_bytes: int = 2) -> dict:
    """Operations and least bytes of the held experts' products of ALL
    layers for ``rows`` sequences at the expected share of the
    assignments. Bytes: each pass reads the held experts' weights once a
    step and reads and writes each routed row once, all in the 16-bit
    compute type."""
    seq, layers = int(traffic["seq_len"]), cfg["num_hidden_layers"]
    routed = rows * 2 * seq * expert_rows_per_token(cfg)
    steps = rows / int(traffic["batch_size"])
    weights = _held(cfg) * 3 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"] * act_bytes
    per_pass = steps * weights + 2 * routed * cfg["hidden_size"] * act_bytes
    return {"flops": PASSES[mode] * layers * routed
            * expert_flops_per_row(cfg),
            "bytes": PASSES[mode] * layers * per_pass}
