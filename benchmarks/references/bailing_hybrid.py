"""Plain float32 reference of the ``bailing_hybrid`` decoder, one chip's
share.

With ``H = hidden_size``, RMSNorm ``x * rsqrt(mean(x^2) + eps) * g``, no
bias anywhere:

    x = embed[ids]
    per layer:  h = x + op(RMSNorm_op(x));  x = h + ffn(RMSNorm_ffn(h))
    logits = RMSNorm_out(x) @ W_head                  (an untied head)

- ``op`` of a ``linear_attention`` layer, Kimi delta attention over the
  held heads of ``d = head_dim``: ``q, k, v = SiLU(conv(x W_{q,k,v}))``
  (depthwise, causal, ``short_conv_kernel_size`` taps indexed by the lag);
  ``q, k`` divided by ``sqrt(sum^2 + 1e-6)`` per head; ``β = σ(x W_b)``
  a head; ``g = lower · σ(exp(A_log) (x W_f + dt_bias))`` a key channel;
  TOKEN BY TOKEN ``S_t = (I - β_t k_t k_tᵀ) Diag(exp g_t) S_{t-1} + β_t
  k_t v_tᵀ`` from zero and ``o_t = S_tᵀ q_t / sqrt(d)``; then
  ``(RMSNorm_d(o) ⊙ σ(x W_g)) W_o``.
- ``op`` of a ``latent_attention`` layer over the held heads:
  ``[q_nope | q_rope] = x W_q``; ``[c | k_rope] = x W_kv_a``,
  ``c = RMSNorm(c)``; ``[k_nope | v] = c W_kv_b``; ``k = [k_nope |
  k_rope]`` (one ``k_rope`` for all heads); RMSNorm over each head's q and
  k; rotary positions on the rope channels, pairs interleaved (taken to
  halves, then ``rotate_half``); ``softmax(q kᵀ / sqrt(d_qk) + causal)
  v``, times ``σ(x w_h)`` a head, ``@ W_o``.
- ``ffn`` of the first ``first_k_dense_replace`` layers:
  ``W_2(silu(W_1 x) * W_3 x)``; of the others: ``s = σ(x W_r)`` over ALL
  ``router_experts``; ``c = s + expert_bias``; the ``n_group`` groups of
  consecutive experts scored by the sum of their two largest ``c``; the
  best ``topk_group`` kept, ``c`` set to 0 in the others; the
  ``num_experts_per_tok`` largest ``c``; weights ``s`` at the selected
  over their sum + 1e-20, times ``routed_scaling_factor``
  (``transformers``' ``deepseek_v3`` ``get_topk_indices``);
  ``y = sum_j w_j E_j(x)`` over the selected experts THIS CHIP HOLDS,
  plus the shared expert of every token, unweighted.
- Loss: next-token cross-entropy, mean over every position of every row.

Departures, stated: this chip holds ``num_attention_heads`` heads of each
attention layer and ``held_experts`` of each sparse layer's experts; what
the absent heads and experts would add is left out and nothing stands in
for it. The gate's form, the norms' placement and the initialisation are
the configuration's ``assumed``.

Nothing here imports the system under test. Everything is ``jax.numpy``
in float32 at matmul precision ``highest``, with no kernel: the delta
rule is the recurrence a token at a time (a ``lax.scan``, checkpointed a
segment of ``SEGMENT`` tokens at a time so that its gradient fits at
8,192 positions; the mathematics unchanged), latent attention forms the
scores of one block of queries at a time, every held expert is computed
for every token and masked by its weight, and each layer is recomputed in
the backward pass. The parameter LAYOUT (names and shapes,
``param_shapes``) is the program's, so that one set of seeded weights
feeds both sides.

``quant`` is the hook for the lower-precision control: a function applied
to both operands of every matrix product (of the delta rule, to q, k and
v as they enter the recurrence; the state stays float32). The cell's
control is ``fp8_e4m3``. ``fault`` plants ``no_erase``: the delta rule's
erase term ``-β k kᵀ`` left out.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: rows of queries whose scores against all keys exist at once
QUERY_CHUNK = 1024
#: tokens of the delta rule whose states the backward pass recomputes
#: from the segment's first one
SEGMENT = 64
#: the faults ``make_loss_and_grad`` can plant
FAULTS = ("no_erase",)


# ------------------------------------------------------------ parameters

def held_experts(cfg: dict) -> tuple:
    """Ids of the experts this chip holds in every sparse layer."""
    return tuple(cfg["held_experts"])


def param_shapes(cfg: dict) -> dict:
    """Names and shapes of every parameter, as a nested dict of tuples."""
    H, I, M = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["moe_intermediate_size"])
    S = cfg["num_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, taps = cfg["kv_lora_rank"], cfg["short_conv_kernel_size"]
    E, G = cfg["router_experts"], len(held_experts(cfg))
    tree = {"embed": {"embedding": (cfg["vocab_size"], H)},
            "out_norm": {"scale": (H,)},
            "lm_head": {"kernel": (H, cfg["vocab_size"])}}
    for i, kind in enumerate(cfg["layer_types"]):
        block = {"operator_norm": {"scale": (H,)},
                 "ffn_norm": {"scale": (H,)}}
        if kind == "linear_attention":
            block["kda"] = {
                **{n: {"kernel": (H, h * d)} for n in "qkvfg"},
                **{f"{n}_conv": (taps, h * d) for n in "qkv"},
                "b": {"kernel": (H, h)}, "A_log": (h,), "dt_bias": (h * d,),
                "o_norm": {"scale": (d,)}, "o": {"kernel": (h * d, H)}}
        else:
            block["attention"] = {
                "q": {"kernel": (H, h * (dn + dr))},
                "kv_a": {"kernel": (H, r + dr)},
                "kv_a_norm": {"scale": (r,)},
                "kv_b": {"kernel": (r, h * (dn + dv))},
                "q_norm": {"scale": (dn + dr,)},
                "k_norm": {"scale": (dn + dr,)},
                "gate": {"kernel": (H, h)}, "o": {"kernel": (h * dv, H)}}
        if i < cfg["first_k_dense_replace"]:
            block["mlp"] = {"w1": {"kernel": (H, I)},
                            "w3": {"kernel": (H, I)},
                            "w2": {"kernel": (I, H)}}
        else:
            block["moe"] = {
                "router": {"kernel": (H, E), "expert_bias": (E,)},
                "experts": {"w1": (G, H, M), "w3": (G, H, M),
                            "w2": (G, M, H)},
                "shared": {"w1": {"kernel": (H, S)},
                           "w3": {"kernel": (H, S)},
                           "w2": {"kernel": (S, H)}}}
        tree[f"block_{i}"] = block
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _draw(key, names, shape, std):
    """One leaf (configuration's ``assumed.weights``)."""
    last = names[-1]
    if last in ("expert_bias", "dt_bias"):
        return jnp.zeros(shape, jnp.float32)
    if last == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1., 16.))
    if last.endswith("_conv"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    v = std * jax.random.normal(key, shape, jnp.float32)
    return v + 1.0 if last == "scale" else v


def draw_weights(cfg: dict, seed: int):
    """Every leaf from the seed, float32, in ONE jitted call on the
    default device (``_draw``); ``expert_bias`` zero (``make_params``
    sets it)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    std = float(cfg["initializer_range"])

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), tuple(k.key for k in path),
                  shape, std)
            for i, (path, shape) in enumerate(leaves)])

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


def route(scores, bias, cfg: dict):
    """(ids [..., k], weights [..., k]) of the grouped selection (module
    docstring) from the sigmoid ``scores`` [..., E]."""
    k, n_group = int(cfg["num_experts_per_tok"]), int(cfg["n_group"])
    E = scores.shape[-1]
    choice = scores + jax.lax.stop_gradient(bias)
    groups = choice.reshape(*choice.shape[:-1], n_group, E // n_group)
    group_scores = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)
    _, kept = jax.lax.top_k(group_scores, int(cfg["topk_group"]))
    mask = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=jnp.int32), -2) > 0
    mask = jnp.repeat(mask, E // n_group, axis=-1)
    _, ids = jax.lax.top_k(jnp.where(mask, choice, 0.0), k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return ids, weights * cfg["routed_scaling_factor"]


@functools.partial(jax.jit, static_argnames=("cfg_json", "iters"))
def balance_bias(scores, tolerance, cfg_json: str, iters: int = 3000):
    """A selection bias under which every expert's load under the grouped
    selection of ``scores`` [..., E] lies within ``tolerance`` of the mean
    ``N k / E``: ``b_e <- b_e + gamma_t (mean - load_e) / mean`` from zero,
    each expert by its own error, ``gamma_t`` a twentieth of the scores'
    spread shrinking as ``1 / sqrt(1 + t / 100)``, until every load is
    inside (or ``iters`` are spent; the best bias seen is kept). A step
    the same for every expert and set by the worst one's error kicks the
    nearly balanced ones about, and on some seeds never settles. Returns
    (bias, worst ``|load/mean - 1|``)."""
    cfg = json.loads(cfg_json)
    scores = scores.reshape(-1, scores.shape[-1])
    n, e = scores.shape
    mean = n * int(cfg["num_experts_per_tok"]) / e
    spread = 0.05 * jnp.std(scores)

    def error(bias):
        ids, _ = route(scores, bias, cfg)
        loads = jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), (0, 1))
        return (mean - loads) / mean

    def worst(err):
        return jnp.max(jnp.abs(err))

    def cond(c):
        t, _, _, _, best_err = c
        return (t < iters) & (worst(best_err) > tolerance)

    def body(c):
        t, bias, err, best, best_err = c
        bias = bias + spread / jnp.sqrt(1.0 + t / 100.0) * err
        err = error(bias)
        better = worst(err) < worst(best_err)
        return (t + 1, bias, err, jnp.where(better, bias, best),
                jnp.where(better, err, best_err))

    zero = jnp.zeros((e,), jnp.float32)
    err = error(zero)
    *_, bias, err = jax.lax.while_loop(cond, body, (0, zero, err, zero, err))
    return bias, worst(err)


_BIAS_CACHE = {}


def calibrated_biases(cfg: dict, seed: int, params) -> dict:
    """{layer index: expert_bias [E]} for every sparse layer, layer after
    layer, on one seeded batch of the traffic's shape followed through
    the layers (configuration's ``assumed.expert_bias``)."""
    key = (json.dumps(cfg, sort_keys=True), seed)
    if key in _BIAS_CACHE:
        return _BIAS_CACHE[key]
    rng = np.random.default_rng([seed % (2 ** 31), 0xCA11B])
    ids = jnp.asarray(rng.integers(
        0, cfg["vocab_size"],
        (int(cfg["calibration_batch"]), int(cfg["calibration_seq_len"])),
        dtype=np.int32))
    cfg_json = json.dumps(cfg, sort_keys=True)
    operator = jax.jit(lambda block, x, kind: _operator(block, x, cfg, kind),
                       static_argnums=2)
    ffn = jax.jit(lambda block, h, y: _ffn(block, h, y, cfg))
    x = jax.jit(lambda table: table[ids])(params["embed"]["embedding"])
    tolerance = float(cfg["expert_bias_tolerance"])
    biases, worst = {}, {}
    for i, kind in enumerate(cfg["layer_types"]):
        block = params[f"block_{i}"]
        h, y = operator(block, x, kind)
        if "moe" in block:
            scores = jax.jit(router_scores)(
                block["moe"]["router"]["kernel"], y)
            bias, err = balance_bias(scores, tolerance, cfg_json)
            biases[i], worst[i] = np.asarray(bias), float(err)
            block = dict(block, moe=dict(block["moe"], router=dict(
                block["moe"]["router"], expert_bias=bias)))
        x = ffn(block, h, y)
    off = {i: w for i, w in worst.items() if w > tolerance}
    if off:
        raise RuntimeError(f"expert_bias: loads not within tolerance "
                           f"{tolerance}: {off}")
    _BIAS_CACHE[key] = biases
    return biases


def make_params(cfg: dict, seed: int):
    """The seeded weights with each sparse layer's ``expert_bias``
    calibrated (``calibrated_biases``), as float32 numpy arrays: made on
    the device and fetched, so that neither side keeps a second copy
    there."""
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


def _conv(v, taps):
    """``c_t = sum_j taps[j] * v[t - j]``, ``v`` zero before position 0."""
    c = jnp.zeros_like(v)
    for j in range(taps.shape[0]):
        c = c + taps[j] * jnp.pad(v, ((0, 0), (j, 0), (0, 0)))[:, :v.shape[1]]
    return c


def delta_rule(q, k, v, g, beta, erase: bool = True):
    """The recurrence a token at a time: ``q``, ``k``, ``g`` [b, L, h, d],
    ``v`` [b, L, h, dv], ``beta`` [b, L, h] -> ``o`` [b, L, h, dv],
    ``o_t = S_tᵀ q_t / sqrt(d)``. The scan runs over segments of
    ``SEGMENT`` tokens, each recomputed in the backward pass from its
    first state. ``erase`` False plants the fault: ``S_t = D_t S_{t-1} +
    β_t k_t v_tᵀ``."""
    b, L, h, d = q.shape
    dv = v.shape[-1]
    seg = min(SEGMENT, L)
    n = -(-L // seg)

    def pad(a):
        return jnp.pad(a, [(0, 0), (0, n * seg - L)] + [(0, 0)] * (a.ndim - 2))

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", S, kt, precision=HIGHEST)
        u = bt[..., None] * (vt - seen) if erase else bt[..., None] * vt
        S = S + kt[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=HIGHEST)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    xs = [jnp.moveaxis(pad(a), 1, 0) for a in (q, k, v, g, beta)]
    xs = [a.reshape(n, seg, *a.shape[1:]) for a in xs]
    S0 = jnp.zeros((b, h, d, dv), jnp.float32)
    _, o = jax.lax.scan(segment, S0, xs)
    o = o.reshape(n * seg, b, h, dv)[:L]
    return jnp.moveaxis(o, 0, 1) / jnp.sqrt(jnp.float32(d))


def _kda(p, x, cfg, quant, fault=None):
    b, L, _ = x.shape
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]

    def qkv(n):
        y = jax.nn.silu(_conv(_mm(x, p[n]["kernel"], quant), p[f"{n}_conv"]))
        return y.reshape(b, L, h, d)

    def l2(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q, k, v = l2(qkv("q")), l2(qkv("k")), qkv("v")
    beta = jax.nn.sigmoid(_mm(x, p["b"]["kernel"], quant))
    f = (_mm(x, p["f"]["kernel"], quant) + p["dt_bias"]).reshape(b, L, h, d)
    g = float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * f)
    if quant is not None:
        q, k, v = quant(q, -1), quant(k, -1), quant(v, -1)
    o = delta_rule(q, k, v, g, beta, erase=fault != "no_erase")
    o = _rms(o, p["o_norm"]["scale"], eps).reshape(b, L, h * d)
    o = o * jax.nn.sigmoid(_mm(x, p["g"]["kernel"], quant))
    return _mm(o, p["o"]["kernel"], quant)


def _rope(x, theta):
    """x [b, L, heads, d]: ``x * cos + rotate_half(x) * sin``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def _mla(p, x, cfg, quant):
    b, L, _ = x.shape
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], \
        float(cfg["rope_theta"])
    q = _mm(x, p["q"]["kernel"], quant).reshape(b, L, h, dn + dr)
    ckr = _mm(x, p["kv_a"]["kernel"], quant)
    c = _rms(ckr[..., :r], p["kv_a_norm"]["scale"], eps)
    kv = _mm(c, p["kv_b"]["kernel"], quant).reshape(b, L, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        ckr[:, :, None, r:], (b, L, h, dr))], -1)
    v = kv[..., dn:]
    q = _rms(q, p["q_norm"]["scale"], eps)
    k = _rms(k, p["k_norm"]["scale"], eps)

    def rotate(y):
        rope = y[..., dn:]
        if cfg["rope_interleave"]:
            # pairs (2i, 2i + 1) to halves
            rope = jnp.concatenate([rope[..., 0::2], rope[..., 1::2]], -1)
        return jnp.concatenate([y[..., :dn], _rope(rope, theta)], -1)

    q, k = rotate(q), rotate(k)
    chunk = min(L, QUERY_CHUNK)

    @jax.checkpoint
    def one(qc, first):
        kk, vv = k, v
        if quant is not None:
            qc, kk = quant(qc, -1), quant(kk, -1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc, kk, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(dn + dr))
        seen = jnp.arange(L)[None, :] <= first + jnp.arange(chunk)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        if quant is not None:
            probs, vv = quant(probs, -1), quant(vv, 1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vv, precision=HIGHEST)

    qs = jnp.moveaxis(q.reshape(b, L // chunk, chunk, h, dn + dr), 1, 0)
    out = jax.lax.map(lambda a: one(*a),
                      (qs, jnp.arange(L // chunk) * chunk))
    out = jnp.moveaxis(out, 0, 1).reshape(b, L, h, dv)
    out = out * jax.nn.sigmoid(_mm(x, p["gate"]["kernel"], quant))[..., None]
    return _mm(out.reshape(b, L, h * dv), p["o"]["kernel"], quant)


def _gated(x, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def router_scores(kernel, x, quant=None):
    return jax.nn.sigmoid(_mm(x, kernel, quant))


def _moe(p, x, cfg, quant):
    """This chip's share: every held expert over every token, masked by
    the token's weight for it (zero where it did not select it), and the
    shared expert."""
    scores = router_scores(p["router"]["kernel"], x, quant)
    ids, weights = route(scores, p["router"]["expert_bias"], cfg)

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
    s = p["shared"]
    return total + _gated(x, s["w1"]["kernel"], s["w3"]["kernel"],
                          s["w2"]["kernel"], quant)


def _operator(block, x, cfg, kind, quant=None, fault=None):
    """(h, RMSNorm_ffn(h)) with ``h = x + op(RMSNorm_op(x))``."""
    eps = cfg["rms_norm_eps"]
    y = _rms(x, block["operator_norm"]["scale"], eps)
    if kind == "linear_attention":
        y = _kda(block["kda"], y, cfg, quant, fault)
    elif kind == "latent_attention":
        y = _mla(block["attention"], y, cfg, quant)
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


def forward(params, ids, cfg: dict, quant=None, fault=None):
    """Float32 logits [rows, L, vocab]; every layer recomputed in the
    backward pass."""
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(cfg["layer_types"]):
        def layer(block, x, kind=kind):
            h, y = _operator(block, x, cfg, kind, quant, fault)
            return _ffn(block, h, y, cfg, quant)
        x = jax.checkpoint(layer)(params[f"block_{i}"], x)
    x = _rms(x, params["out_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"], quant)


# -------------------------------------------------------------- training

def loss_sum(params, ids, labels, cfg, quant=None, fault=None):
    """Summed next-token cross-entropy of a block of rows: ``labels``
    [rows, L] holds each position's next id."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg, quant, fault),
                              axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def layer(block, x, cfg, kind, quant=None, fault=None):
    """One layer: ``x`` -> ``h + ffn(RMSNorm_ffn(h))``."""
    h, y = _operator(block, x, cfg, kind, quant, fault)
    return _ffn(block, h, y, cfg, quant)


def make_loss_and_grad(cfg, batch: int, block: int, quant=None, used=None,
                       fault=None):
    """(params, ids [batch, L], labels [batch, L], key) -> (mean loss over
    every position of every row, gradient of it as float32 numpy arrays),
    accumulated on the host over blocks of ``block`` rows. ``key`` is not
    used: the model drops nothing. ``used`` (default: all) plants the
    half-batch fault: only the first ``used`` rows count, the mean over
    them; a batch of one row has no half, so ``used=0`` there leaves out
    the second half of the row's POSITIONS and takes the mean over the
    rest. ``fault`` (``FAULTS``) plants a fault in the mathematics.

    The gradient is taken a layer at a time, each layer's rule a program
    of its own: the forward pass keeps every layer's input, the head
    gives the loss and the cotangent of the stack's output, and the
    layers are gone through backwards, each recomputed inside its rule,
    its parameters' gradient leaving the device before the next rule
    runs, so that it fits beside the parameters at the cell's size."""
    used = batch if used is None else used
    half_row = used == 0
    if half_row:
        used = batch
    block = max(1, min(block, used))
    if used % block:
        raise ValueError(f"{used} rows do not divide into blocks of {block}")
    if fault not in (None,) + FAULTS:
        raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
    kinds = list(cfg["layer_types"])

    forward_layer = jax.jit(
        lambda blk, x, kind: layer(blk, x, cfg, kind, quant, fault),
        static_argnums=2)

    @functools.partial(jax.jit, static_argnums=3)
    def backward_layer(blk, x, g, kind):
        _, rule = jax.vjp(lambda blk, x: layer(blk, x, cfg, kind, quant,
                                               fault), blk, x)
        return rule(g)

    @jax.jit
    def embed(table, ids):
        return table[ids]

    @jax.jit
    def backward_embed(table, ids, g):
        return jnp.zeros_like(table).at[ids].add(g)

    def head_loss(top, x, labels, weights):
        x = _rms(x, top["out_norm"]["scale"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(x, top["lm_head"]["kernel"], quant),
                                  axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * weights)

    backward_head = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1)))

    def to_host(tree, scale):
        return jax.tree_util.tree_map(lambda g: np.asarray(g) * scale, tree)

    def block_grad(p, ids, labels, weights, scale):
        """(loss sum, the block's gradient on the host, scaled)."""
        xs = [embed(p["embed"]["embedding"], ids)]
        for i, kind in enumerate(kinds):
            xs.append(forward_layer(p[f"block_{i}"], xs[-1], kind))
        top = {k: p[k] for k in ("out_norm", "lm_head")}
        loss, (g_top, g) = backward_head(top, xs.pop(), labels, weights)
        grads = to_host(g_top, scale)
        for i in reversed(range(len(kinds))):
            g_block, g = backward_layer(p[f"block_{i}"], xs.pop(), g,
                                        kinds[i])
            grads[f"block_{i}"] = to_host(g_block, scale)
            del g_block
        grads["embed"] = to_host(
            {"embedding": backward_embed(p["embed"]["embedding"], ids, g)},
            scale)
        return float(loss), grads

    def loss_and_grad(params, ids, labels, key=None):
        p = jax.tree_util.tree_map(jnp.asarray, params)
        L = ids.shape[1]
        count = used * L
        if half_row:
            count = used * (L // 2)
        scale = np.float32(1.0 / count)
        weights = jnp.ones((block, L), jnp.float32)
        if half_row:
            weights = weights * (jnp.arange(L) < L // 2)
        total, grads = 0.0, None
        for start in range(0, used, block):
            loss, g = block_grad(p, jnp.asarray(ids[start:start + block]),
                                 jnp.asarray(labels[start:start + block]),
                                 weights, scale)
            total += loss
            grads = g if grads is None else jax.tree_util.tree_map(
                np.add, grads, g)
        return total * float(scale), grads

    return loss_and_grad
