"""Plain float32 reference of the ``lfm2_moe`` decoder, one chip's share.

Follows ``transformers``' ``lfm2_moe`` (modeling_lfm2_moe.py) with
``H = hidden_size``, RMSNorm ``x * rsqrt(mean(x^2) + eps) * g``:

    x = embed[ids]
    per layer:  h = x + op(RMSNorm_op(x));  x = h + ffn(RMSNorm_ffn(h))
    logits = RMSNorm_out(x) @ embed.T                 (tied embeddings)

- ``op`` of a ``conv`` layer: ``[B, C, u] = split3(x @ W_in)``,
  ``v = B * u``, ``c_t = sum_j k_j * v_{t-j}`` (depthwise, causal,
  ``conv_L_cache`` taps, ``v`` zero before the start), ``(C * c) @ W_out``.
- ``op`` of a ``full_attention`` layer: grouped-query heads (each
  key-value head serves ``heads / kv_heads`` consecutive query heads),
  RMSNorm over each head of q and of k, rotary positions with the halves
  rotated (``rotate_half``), ``softmax(q k^T / sqrt(d) + causal) v``,
  ``@ W_o``.
- ``ffn`` of the first ``num_dense_layers`` layers:
  ``W_2(silu(W_1 x) * W_3 x)``; of the others: ``s = sigmoid(x @ W_g)``
  over ALL ``router_experts`` columns, the ``num_experts_per_tok`` experts
  with the largest ``s + expert_bias`` (the bias steers the selection
  only), weights ``s`` at the selected divided by their sum + 1e-6
  (``norm_topk_prob``), times ``routed_scaling_factor``;
  ``y = sum_j w_j E_j(x)`` over the selected experts THIS CHIP HOLDS
  (``held_experts``), the weights normalised over all selected. What the
  absent experts would add is left out and nothing stands in for it.
- No dropout, no bias. Loss: next-token cross-entropy, mean over every
  position of every row.

Departure from the public code, stated: the taps ``k_j`` are indexed by
the lag ``j`` (torch's ``Conv1d`` weight is the same numbers in reverse
order); with weights drawn from a seed the two are one distribution.

Nothing here imports the system under test. Everything is ``jax.numpy``
in float32 at matmul precision ``highest``, with no kernel: every held
expert is computed for every token and masked by its weight; attention
forms the scores of one key-value head and one chunk of queries at a
time, each layer is recomputed in the backward pass, so that one row of
8,192 positions fits beside the parameters. The parameter LAYOUT (names
and shapes, ``param_shapes``) is the program's, so that one set of seeded
weights feeds both sides.

``quant`` is the hook for the lower-precision control: a function applied
to both operands of every matrix product. The cell's control is
``fp8_e4m3``.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_CHUNK = 1024


# ------------------------------------------------------------ parameters

def held_experts(cfg: dict) -> tuple:
    """Ids of the experts this chip holds in every sparse layer."""
    return tuple(cfg.get("held_experts", range(cfg["num_experts"])))


def param_shapes(cfg: dict) -> dict:
    """Names and shapes of every parameter, as a nested dict of tuples."""
    H, I, M = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["moe_intermediate_size"])
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = H // h
    E, G = cfg["router_experts"], len(held_experts(cfg))
    tree = {"embed": {"embedding": (cfg["vocab_size"], H)},
            "out_norm": {"scale": (H,)}}
    for i, kind in enumerate(cfg["layer_types"]):
        block = {"operator_norm": {"scale": (H,)},
                 "ffn_norm": {"scale": (H,)}}
        if kind == "conv":
            block["conv"] = {"in_proj": {"kernel": (H, 3 * H)},
                             "kernel": (cfg["conv_L_cache"], H),
                             "out_proj": {"kernel": (H, H)}}
        else:
            block["attention"] = {
                "q": {"kernel": (H, h * d)}, "k": {"kernel": (H, g * d)},
                "v": {"kernel": (H, g * d)}, "out": {"kernel": (h * d, H)},
                "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)}}
        if i < cfg["num_dense_layers"]:
            block["mlp"] = {"w1": {"kernel": (H, I)},
                            "w3": {"kernel": (H, I)},
                            "w2": {"kernel": (I, H)}}
        else:
            block["moe"] = {
                "router": {"kernel": (H, E), "expert_bias": (E,)},
                "experts": {"w1": (G, H, M), "w3": (G, H, M),
                            "w2": (G, M, H)}}
        tree[f"block_{i}"] = block
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def draw_weights(cfg: dict, seed: int):
    """Every leaf from the seed, float32, in ONE jitted call on the
    default device: normal(0, initializer_range), norm scales 1 + that,
    ``expert_bias`` zero (``make_params`` sets it)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    std = float(cfg["initializer_range"])

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            last = path[-1].key
            if last == "expert_bias":
                out.append(jnp.zeros(shape, jnp.float32))
                continue
            v = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            out.append(v + 1.0 if last == "scale" else v)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def balance_bias(scores, k: int, tolerance, iters: int = 2000):
    """A selection bias under which every expert's load — the number of
    tokens of ``scores`` [..., E] that pick it among their ``k`` largest
    ``score + bias`` — lies within ``tolerance`` of the mean ``N k / E``:
    ``b_e <- b_e + gamma_t * sign(mean - load_e)`` from zero, the step
    ``gamma_t`` a tenth of the scores' spread times the worst expert's
    ``|load/mean - 1|`` (so it shrinks as the loads near the mean), until
    every load is inside (or ``iters`` are spent). Returns (bias, worst
    ``|load/mean - 1|``)."""
    scores = scores.reshape(-1, scores.shape[-1])
    n, e = scores.shape
    mean = n * k / e
    spread = 0.1 * jnp.std(scores)

    def error(bias):
        _, ids = jax.lax.top_k(scores + bias, k)
        loads = jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), (0, 1))
        return (mean - loads) / mean

    def cond(c):
        t, _, err = c
        return (t < iters) & (jnp.max(jnp.abs(err)) > tolerance)

    def body(c):
        t, bias, err = c
        bias = bias + spread * jnp.max(jnp.abs(err)) * jnp.sign(err)
        return t + 1, bias, error(bias)

    zero = jnp.zeros((e,), jnp.float32)
    _, bias, err = jax.lax.while_loop(cond, body, (0, zero, error(zero)))
    return bias, jnp.max(jnp.abs(err))


_BIAS_CACHE = {}


def calibrated_biases(cfg: dict, seed: int, params) -> dict:
    """{layer index: expert_bias [E]} for every sparse layer, layer after
    layer: the published checkpoint's bias is the converged state of its
    load balancing, under which each expert sees about tokens x k / E
    assignments; a zero bias under random weights does not (one expert
    read 4.2 times the mean). So the forward pass of one seeded batch of
    the traffic's shape (``calibration_batch`` x ``calibration_seq_len``
    ids uniform over the vocabulary slice) is followed through the
    layers, and at each sparse layer the bias is balanced on that batch's
    scores (``balance_bias``) before the layer's output goes on."""
    key = (json.dumps(cfg, sort_keys=True), seed)
    if key in _BIAS_CACHE:
        return _BIAS_CACHE[key]
    rng = np.random.default_rng([seed % (2 ** 31), 0xCA11B])
    ids = jnp.asarray(rng.integers(
        0, cfg["vocab_size"],
        (int(cfg["calibration_batch"]), int(cfg["calibration_seq_len"])),
        dtype=np.int32))
    operator = jax.jit(lambda block, x, kind: _operator(block, x, cfg, kind),
                       static_argnums=2)
    ffn = jax.jit(lambda block, h, y: _ffn(block, h, y, cfg))
    x = jax.jit(lambda table: table[ids])(params["embed"]["embedding"])
    biases, worst = {}, {}
    for i, kind in enumerate(cfg["layer_types"]):
        block = params[f"block_{i}"]
        h, y = operator(block, x, kind)
        if "moe" in block:
            scores = jax.jit(router_scores)(
                block["moe"]["router"]["kernel"], y)
            bias, err = balance_bias(
                scores, int(cfg["num_experts_per_tok"]),
                float(cfg["expert_bias_tolerance"]))
            biases[i], worst[i] = np.asarray(bias), float(err)
            block = dict(block, moe=dict(block["moe"], router=dict(
                block["moe"]["router"], expert_bias=bias)))
        x = ffn(block, h, y)
    off = {i: w for i, w in worst.items()
           if w > float(cfg["expert_bias_tolerance"])}
    if off:
        raise RuntimeError(f"expert_bias: loads not within tolerance "
                           f"{cfg['expert_bias_tolerance']}: {off}")
    _BIAS_CACHE[key] = biases
    return biases


def make_params(cfg: dict, seed: int):
    """The seeded weights with each sparse layer's ``expert_bias``
    calibrated (``calibrated_biases``), as float32 numpy arrays: made on
    the device in one jitted call and fetched, so that neither side keeps
    a second copy of 2 GB there (the reference follows three Adam steps
    beside them: PERF.md section 4)."""
    params = draw_weights(cfg, seed)
    for i, bias in calibrated_biases(cfg, seed, params).items():
        params[f"block_{i}"]["moe"]["router"]["expert_bias"] = bias
    return jax.tree_util.tree_map(np.asarray, params)


# --------------------------------------------------------------- forward

def fp8_e4m3(x, axis):
    """Round to float8 e4m3 (three mantissa bits) and back, scaled so the
    tensor's largest magnitude sits at the format's largest (448)."""
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, w, quant=None):
    """a [..., k] @ w [k, n] at full float32 precision."""
    if quant is not None:
        a, w = quant(a, -1), quant(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x [b, L, heads, d]: ``x * cos + rotate_half(x) * sin``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def _conv(p, x, cfg, quant):
    gate_b, gate_c, u = jnp.split(_mm(x, p["in_proj"]["kernel"], quant), 3,
                                  axis=-1)
    v = gate_b * u
    c = jnp.zeros_like(v)
    for j in range(cfg["conv_L_cache"]):
        shifted = jnp.pad(v, ((0, 0), (j, 0), (0, 0)))[:, :v.shape[1]]
        c = c + p["kernel"][j] * shifted
    return _mm(gate_c * c, p["out_proj"]["kernel"], quant)


def _attention(p, x, cfg, quant):
    b, L, H = x.shape
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, n = H // h, h // g
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    q = _mm(x, p["q"]["kernel"], quant).reshape(b, L, h, d)
    k = _mm(x, p["k"]["kernel"], quant).reshape(b, L, g, d)
    v = _mm(x, p["v"]["kernel"], quant).reshape(b, L, g, d)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), theta)
    chunk = min(L, QUERY_CHUNK)
    # [kv head, chunk of queries, b, chunk, n, d]
    qs = q.reshape(b, L // chunk, chunk, g, n, d).transpose(3, 1, 0, 2, 4, 5)
    ks, vs = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)   # [g,b,L,d]

    @jax.checkpoint
    def one(qc, kg, vg, first):
        """One chunk of queries of the query heads one key-value head
        serves, against all its keys."""
        if quant is not None:
            qc, kg = quant(qc, -1), quant(kg, -1)
        scores = jnp.einsum("bqnd,bkd->bnqk", qc, kg, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(L)[None, :] <= first + jnp.arange(chunk)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        if quant is not None:
            probs, vg = quant(probs, -1), quant(vg, -2)
        return jnp.einsum("bnqk,bkd->bqnd", probs, vg, precision=HIGHEST)

    def head(args):
        qg, kg, vg = args
        firsts = jnp.arange(L // chunk) * chunk
        return jax.lax.map(lambda a: one(a[0], kg, vg, a[1]), (qg, firsts))

    ctx = jax.lax.map(head, (qs, ks, vs))       # [g, chunks, b, chunk, n, d]
    ctx = ctx.transpose(2, 1, 3, 0, 4, 5).reshape(b, L, h * d)
    return _mm(ctx, p["out"]["kernel"], quant)


def _gated(x, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def router_scores(kernel, x, quant=None):
    return jax.nn.sigmoid(_mm(x, kernel, quant))


def _moe(p, x, cfg, quant):
    """This chip's share: every held expert over every token, masked by
    the token's weight for it (zero where it did not select it)."""
    k = int(cfg["num_experts_per_tok"])
    scores = router_scores(p["router"]["kernel"], x, quant)
    _, ids = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["router"]["expert_bias"]), k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    weights = weights * cfg["routed_scaling_factor"]

    @jax.checkpoint
    def one(total, expert):
        e, w1, w3, w2 = expert
        mine = jnp.sum(jnp.where(ids == e, weights, 0.0), -1, keepdims=True)
        return total + mine * _gated(x, w1, w3, w2, quant), None

    E = p["experts"]
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.asarray(held_experts(cfg), jnp.int32), E["w1"], E["w3"],
         E["w2"]))
    return total


def _operator(block, x, cfg, kind, quant=None):
    """(h, RMSNorm_ffn(h)) with ``h = x + op(RMSNorm_op(x))``."""
    eps = cfg["norm_eps"]
    y = _rms(x, block["operator_norm"]["scale"], eps)
    if kind == "conv":
        y = _conv(block["conv"], y, cfg, quant)
    elif kind == "full_attention":
        y = _attention(block["attention"], y, cfg, quant)
    else:
        raise ValueError(f"layer type {kind!r}")
    h = x + y
    return h, _rms(h, block["ffn_norm"]["scale"], eps)


def _ffn(block, h, y, cfg, quant=None):
    if "moe" in block:
        return h + _moe(block["moe"], y, cfg, quant)
    m = block["mlp"]
    return h + _gated(y, m["w1"]["kernel"], m["w3"]["kernel"],
                      m["w2"]["kernel"], quant)


def forward(params, ids, cfg: dict, quant=None):
    """Float32 logits [rows, L, vocab]; every layer recomputed in the
    backward pass."""
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(cfg["layer_types"]):
        def layer(block, x, kind=kind):
            h, y = _operator(block, x, cfg, kind, quant)
            return _ffn(block, h, y, cfg, quant)
        x = jax.checkpoint(layer)(params[f"block_{i}"], x)
    x = _rms(x, params["out_norm"]["scale"], cfg["norm_eps"])
    return _mm(x, params["embed"]["embedding"].T, quant)


# -------------------------------------------------------------- training

def loss_sum(params, ids, labels, cfg, quant=None):
    """Summed next-token cross-entropy of a block of rows: ``labels``
    [rows, L] holds each position's next id."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg, quant), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def make_loss_and_grad(cfg, batch: int, block: int, quant=None, used=None):
    """(params, ids [batch, L], labels [batch, L], key) -> (mean loss over
    every position of every row, gradient of it as float32 numpy arrays),
    accumulated over blocks of ``block`` rows so that the float32
    activations of the whole batch never live at once; the running sum is
    donated from block to block, and the gradient leaves the device, so
    that the Adam steps the driver follows fit beside it. ``key`` (the
    step's dropout key) is not used: the model drops nothing. ``used``
    (default: all) plants a fault for the tests and the fault readings:
    only the first ``used`` rows count, the mean taken over them."""
    used = batch if used is None else used
    if used % block:
        raise ValueError(f"{used} rows do not divide into blocks of {block}")

    @functools.partial(jax.jit, donate_argnums=(3, 4))
    def add_block(params, ids, labels, total, grads):
        l, g = jax.value_and_grad(loss_sum)(params, ids, labels, cfg, quant)
        return total + l, jax.tree_util.tree_map(jnp.add, grads, g)

    def loss_and_grad(params, ids, labels, key=None):
        params = jax.tree_util.tree_map(jnp.asarray, params)
        total = jnp.zeros((), jnp.float32)
        grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        for start in range(0, used, block):
            total, grads = add_block(
                params, jnp.asarray(ids[start:start + block]),
                jnp.asarray(labels[start:start + block]), total, grads)
        scale = np.float32(1.0 / (used * ids.shape[1]))
        return float(total) * float(scale), jax.tree_util.tree_map(
            lambda g: np.asarray(g) * scale, grads)

    return loss_and_grad
