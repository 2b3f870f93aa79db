"""Operations and bytes the mathematics of BERT needs, from the shapes of a
configuration file alone. Counts what the published model computes — a
multiply-add is two operations — never what an implementation executes:
recomputation, padding and a kernel's internals do not appear, so a share
of peak built on these cannot pass 100% by construction.

Imports nothing of the system under test.
"""


def encoder_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product once per token: per layer
    the four attention projections and the two feed-forward matrices."""
    H, I = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * H * H + 2 * H * I)


def attention_block_forward_flops(cfg: dict, seq: int) -> int:
    """One layer's attention block for one sequence: q, k, v and output
    projections, scores and the weighted sum of values."""
    H = cfg["hidden_size"]
    return 2 * seq * 4 * H * H + 4 * seq * seq * H


def forward_flops(cfg: dict, seq: int, n_classes: int = 2) -> int:
    """One sequence through encoder, pooler and head."""
    H = cfg["hidden_size"]
    per_token = 2 * encoder_matmul_params(cfg) * seq
    scores = cfg["num_hidden_layers"] * 4 * seq * seq * H
    return per_token + scores + 2 * H * H + 2 * H * n_classes


def sample_flops(cfg: dict, traffic: dict, mode: str) -> int:
    """One sample or record of the traffic's length. ``mode`` 'train':
    forward and backward (the backward of a matrix product is two
    products); 'serve': forward."""
    return (forward_flops(cfg, int(traffic["seq_len"]), cfg["n_classes"])
            * {"train": 3, "serve": 1}[mode])


def attention_block_needs(cfg: dict, traffic: dict, rows: int, mode: str,
                          act_bytes: int = 2) -> dict:
    """Operations and least bytes of ALL layers' attention blocks for
    ``rows`` sequences. Bytes: each pass reads the block's input and
    writes its output once, in the 16-bit compute type; everything between
    can stay on chip, and the weights, read once a step whatever the
    batch, are left out (so the byte bound is a little low: the safe
    side)."""
    H, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    seq = int(traffic["seq_len"])
    passes = {"train": 3, "serve": 1}[mode]
    flops = passes * layers * rows * attention_block_forward_flops(cfg, seq)
    per_pass = 2 * rows * seq * H * act_bytes
    return {"flops": flops, "bytes": passes * layers * per_pass}
