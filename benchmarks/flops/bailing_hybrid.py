"""Operations and bytes the mathematics of the ``bailing_hybrid`` decoder
needs at this chip's expected share, from the shapes of a configuration
file alone. Counts what the cut model computes — a multiply-add is two
operations; causal latent attention forms ``L (L + 1) / 2`` pairs a
sequence; the delta rule is counted as its recurrence a token at a time
(decay, read, erase-and-write and output of a ``d_k x d_v`` state a head,
``7 d_k d_v + 2 d_v``); a token meets ``num_experts_per_tok * held /
router_experts`` of the held experts on average and the shared expert
once — never what an implementation executes: recomputation, padding,
the chunked form's extra products, a window wider than the expected rows
and a kernel's internals do not appear, so a share of peak built on these
cannot pass 100% by construction.

Imports nothing of the system under test.
"""

PASSES = {"train": 3, "serve": 1}


def _held(cfg: dict) -> int:
    return len(cfg["held_experts"])


def _layers(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in cfg["layer_types"])


def _sparse_layers(cfg: dict) -> int:
    return len(cfg["layer_types"]) - cfg["first_k_dense_replace"]


def expert_rows_per_token(cfg: dict) -> float:
    """Assignments that fall on held experts, per token, expected."""
    return cfg["num_experts_per_tok"] * _held(cfg) / cfg["router_experts"]


def expert_flops_per_row(cfg: dict) -> int:
    """One token through one gated expert."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def kda_layer_forward_flops(cfg: dict, seq: int) -> int:
    """One Kimi delta attention layer for one sequence: the q, k, v, f,
    g, b and output projections, the three short convolutions and the
    recurrence of every held head."""
    H, h, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]
    per_token = 2 * H * (5 * h * d + h) + 2 * h * d * H \
        + 2 * taps * 3 * h * d + h * (7 * d * d + 2 * d)
    return seq * per_token


def mla_layer_forward_flops(cfg: dict, seq: int) -> int:
    """One latent attention layer for one sequence: its projections
    (q, the latent, its up-projection, the gate, the output) and scores
    and weighted values of the causal pairs of every held head."""
    H, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    per_token = 2 * H * h * (dn + dr) + 2 * H * (r + dr) \
        + 2 * r * h * (dn + dv) + 2 * H * h + 2 * h * dv * H
    pairs = seq * (seq + 1) // 2
    return seq * per_token + 2 * pairs * h * (dn + dr + dv)


def forward_flops(cfg: dict, seq: int) -> float:
    """One sequence through the cut stack and the head."""
    H = cfg["hidden_size"]
    shared = cfg["num_shared_experts"] \
        * cfg["moe_shared_expert_intermediate_size"]
    per_token = cfg["first_k_dense_replace"] * 6 * H \
        * cfg["intermediate_size"] \
        + _sparse_layers(cfg) * (
            2 * H * cfg["router_experts"] + 6 * H * shared
            + expert_rows_per_token(cfg) * expert_flops_per_row(cfg)) \
        + 2 * H * cfg["vocab_size"]
    return seq * per_token \
        + _layers(cfg, "linear_attention") * kda_layer_forward_flops(cfg, seq) \
        + _layers(cfg, "latent_attention") * mla_layer_forward_flops(cfg, seq)


def sample_flops(cfg: dict, traffic: dict, mode: str) -> float:
    """One sample (a row of the traffic's length): forward and backward
    (the backward of a matrix product is two products) in 'train'."""
    return forward_flops(cfg, int(traffic["seq_len"])) * PASSES[mode]


def _block_needs(cfg, traffic, rows, mode, kind, layer_flops, act_bytes):
    """Operations and least bytes of ALL layers of ``kind`` for ``rows``
    sequences. Bytes: each pass reads the block's input and writes its
    output once, in the 16-bit compute type; the weights, read once a
    step whatever the batch, are left out (the byte bound a little low:
    the safe side)."""
    seq, layers = int(traffic["seq_len"]), _layers(cfg, kind)
    flops = PASSES[mode] * layers * rows * layer_flops(cfg, seq)
    per_pass = 2 * rows * seq * cfg["hidden_size"] * act_bytes
    return {"flops": flops, "bytes": PASSES[mode] * layers * per_pass}


def attention_block_needs(cfg: dict, traffic: dict, rows: int, mode: str,
                          act_bytes: int = 2) -> dict:
    """The latent attention layers (scope ``block_N/attention``)."""
    return _block_needs(cfg, traffic, rows, mode, "latent_attention",
                        mla_layer_forward_flops, act_bytes)


def kda_needs(cfg: dict, traffic: dict, rows: int, mode: str,
              act_bytes: int = 2) -> dict:
    """The Kimi delta attention layers (scope ``block_N/kda``)."""
    return _block_needs(cfg, traffic, rows, mode, "linear_attention",
                        kda_layer_forward_flops, act_bytes)


def moe_experts_needs(cfg: dict, traffic: dict, rows: int, mode: str,
                      act_bytes: int = 2) -> dict:
    """Operations and least bytes of the held experts' products of ALL
    sparse layers for ``rows`` sequences at the expected share of the
    assignments (the shared expert, outside the scope ``moe/experts``, is
    not among them). Bytes: each pass reads the held experts' weights
    once a step and reads and writes each routed row once, all in the
    16-bit compute type."""
    seq, layers = int(traffic["seq_len"]), _sparse_layers(cfg)
    routed = rows * seq * expert_rows_per_token(cfg)
    steps = rows / int(traffic["batch_size"])
    weights = _held(cfg) * 3 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"] * act_bytes
    per_pass = steps * weights + 2 * routed * cfg["hidden_size"] * act_bytes
    return {"flops": PASSES[mode] * layers * routed
            * expert_flops_per_row(cfg),
            "bytes": PASSES[mode] * layers * per_pass}
