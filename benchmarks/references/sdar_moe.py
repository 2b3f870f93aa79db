"""Plain float32 reference of ``sdar_moe`` block-diffusion TRAINING, one
chip's share.

SDAR is a ``qwen3_moe`` stack (``transformers``' modeling_qwen3_moe.py)
trained as a blockwise masked-diffusion model: the objective and the
training layout are those of block diffusion (BD3-LM, arXiv:2503.09573)
with the masked-diffusion weights of MDLM. With ``H = hidden_size``,
RMSNorm ``x * rsqrt(mean(x^2) + eps) * g``, no bias anywhere, a layer for
rows ``x`` [n, H] is

    y = RMSNorm(x);  q = y Wq  [n, heads, d];  k = y Wk, v = y Wv  [n, kv, d]
    q, k <- RMSNorm over the d of each head, then rotary(theta) at the
            row's POSITION (halves rotated, ``rotate_half``)
    a = softmax(q k^T / sqrt(d) + mask) v    query head on key head // group
    h = x + a Wo
    z = RMSNorm(h);  p = softmax(z Wr) over ALL ``router_experts``
    S = the ``num_experts_per_tok`` largest of p;  w_e = p_e / sum_S p
    out = h + sum_{e in S, e held} w_e * W2_e(silu(W1_e z) * W3_e z)
    logits = RMSNorm(x_last) Whead              Whead is NOT the embedding

and a training step on ids ``x0`` [b, L], block length ``B``, ``K = L /
B`` blocks, mask id ``M``, from the step's key:

    u[b, K] ~ U[0, 1);  t = (1 - eps) u + eps             one t a block a row
    m[b, L] ~ Bernoulli(t of the position's block);  xt = where(m, M, x0)
    input ids [xt ; x0]  (2L rows a sequence), positions [0..L-1 ; 0..L-1]
    row i of the noisy half (block bi) sees: noisy keys j with bj == bi;
                                             clean keys j with bj <  bi
    row i of the clean half (block bi) sees: clean keys j with bj <= bi;
                                             no noisy key
    logits over the NOISY half only, at each position for its own id
    loss = sum_{b, i} m[b, i] / t[b, block(i)] * nll(logits[b, i], x0[b, i])
           / (b * L)

Departures, stated. (1) This chip holds ``held_experts`` of each layer's
``router_experts``; what the absent experts would add is left out and
nothing stands in for it (the weights stay normalised over all selected).
(2) The row's ``not_given`` sizes are set by the family's convention and
listed in the configuration's ``assumed``: ``block_length`` 4, the linear
schedule above (weight ``1 / t``, ``noise_eps`` 1e-3), no auxiliary router
loss (``output_router_logits`` false). (3) The vocabulary is a slice; the
mask id is its last row. (4) ``make_params`` calibrates the seeded router
weights (``calibrated_routers``).

Nothing here imports the system under test, its kernels, its tile table
or ``ops/moe.py``. Everything is ``jax.numpy`` in float32 at matmul
precision ``highest``: the mask is a dense ``[rows, 2L]`` boolean written
from the five lines above, a chunk of query rows of the query heads of
one key-value head at a time; every held expert is computed for every
token and masked by its weight; each layer is recomputed in the backward
pass. The parameter LAYOUT (names and shapes, ``param_shapes``) is the
program's, and ``corrupt`` follows the program's key — flax's static key
folding (``fold_static``) and the order of the two draws — so that both
sides mask the same positions.

``quant`` is the hook for the lower-precision control: a function applied
to both operands of every matrix product. The cell's control is
``fp8_e4m3``. ``fault`` plants a wrong mask for the readings.
"""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: rows of queries whose scores against all keys exist at once, and rows
#: of the noisy half whose float32 logits do
QUERY_CHUNK = 256
HEAD_CHUNK = 2048
#: the faults ``make_loss_and_grad`` can plant in the mask
MASK_FAULTS = ("own_clean_block",)


# ------------------------------------------------------------ parameters

def held_experts(cfg: dict) -> tuple:
    """Ids of the experts this chip holds in every layer."""
    return tuple(cfg.get("held_experts", range(cfg["num_experts"])))


def param_shapes(cfg: dict) -> dict:
    """Names and shapes of every parameter, as a nested dict of tuples."""
    H, M, V = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["vocab_size"])
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    E, G = cfg["router_experts"], len(held_experts(cfg))
    tree = {"embed": {"embedding": (V, H)}, "out_norm": {"scale": (H,)},
            "lm_head": {"kernel": (H, V)}}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"block_{i}"] = {
            "operator_norm": {"scale": (H,)}, "ffn_norm": {"scale": (H,)},
            "attention": {
                "q": {"kernel": (H, h * d)}, "k": {"kernel": (H, g * d)},
                "v": {"kernel": (H, g * d)}, "out": {"kernel": (h * d, H)},
                "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)}},
            "moe": {"router": {"kernel": (H, E)},
                    "experts": {"w1": (G, H, M), "w3": (G, H, M),
                                "w2": (G, M, H)}}}
    return {"decoder": tree}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def leaf_std(cfg: dict, path: tuple) -> float:
    """The standard deviation a leaf is drawn at (``path``: its names
    from the root): ``initializer_range``, but ``embedding_range`` for the
    embedding's rows and ``initializer_range / sqrt(2 * published
    layers)`` for the two projections that write into the residual stream
    (attention's ``out``, the experts' ``w2``): the configuration's
    ``assumed.weights`` says why."""
    if path[-2:] == ("embed", "embedding"):
        return float(cfg["embedding_range"])
    std = float(cfg["initializer_range"])
    if path[-2:] == ("out", "kernel") or path[-1] == "w2":
        return std / (2 * cfg["published"]["num_hidden_layers"]) ** 0.5
    return std


def draw_weights(cfg: dict, seed: int):
    """Every leaf from the seed, float32, in ONE jitted call on the
    default device: normal(0, ``leaf_std``), norm scales 1 + that."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            names = tuple(k.key for k in path)
            v = leaf_std(cfg, names) * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            out.append(v + 1.0 if names[-1] == "scale" else v)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


def sink_experts(preference, held: tuple, k: int) -> tuple:
    """The ``k`` experts the mask id's rows are sent to: of the experts
    in the order the drawn router prefers them for those rows, the first
    held one and the first ``k - 1`` absent ones (all held, or ``k`` of 1:
    the first ``k``) — the chip's expected part of ``k`` experts out of
    all, ``k * held / all``, is one in the configuration the cell runs."""
    order = [int(e) for e in np.argsort(-np.asarray(preference))]
    absent = [e for e in order if e not in held]
    mine = [e for e in order if e in held]
    if not absent or k == 1:
        return tuple(sorted(order[:k]))
    return tuple(sorted(mine[:1] + absent[:k - 1]))


@jax.jit
def _row_means(z, masked):
    """Mean router input of the rows that hold the mask id, and of the
    other rows."""
    z = z.reshape(-1, z.shape[-1])
    w = masked.reshape(-1, 1).astype(z.dtype)
    return (jnp.sum(z * w, 0) / jnp.sum(w),
            jnp.sum(z * (1 - w), 0) / jnp.sum(1 - w))


def sunk_router(kernel, centre, others, sinks: tuple, margin: float):
    """The router's seeded ``kernel`` [H, E] with two components taken
    out of every column: the one along ``others``, the mean input of the
    rows that do not hold the mask id (what every row shares gives each
    expert an offset of its own); and the one along what ``centre``, the
    mean input of the mask id's rows, has beyond that — to the drawn
    router those rows, a quarter of all rows and the same row but for
    what attention adds, are one point, and it sends them to whichever
    eight experts that point prefers. The second is put back into the
    columns of ``sinks`` alone, at the size that gives them ``margin`` in
    the logits of that point; the other rows' mean does not feel it."""
    centre, others, kernel = (np.asarray(a, np.float64)
                              for a in (centre, others, kernel))
    shared = others / np.linalg.norm(others)
    own = centre - (centre @ shared) * shared
    own = own / np.linalg.norm(own)
    kernel = kernel - np.outer(shared, shared @ kernel) \
        - np.outer(own, own @ kernel)
    kernel[:, list(sinks)] += (margin / (centre @ own)) * own[:, None]
    return kernel.astype(np.float32)


def routing_loads(logits, masked, k: int, sinks: tuple) -> tuple:
    """(share of the mask id's rows whose ``k`` experts are exactly
    ``sinks``, largest ``|load / mean - 1|`` of an expert over the other
    rows)."""
    _, ids = jax.lax.top_k(logits, k)
    ids, masked = np.asarray(ids), np.asarray(masked).reshape(-1)
    sunk = np.all(np.sort(ids[masked], -1) == np.asarray(sinks), -1).mean()
    loads = np.bincount(ids[~masked].ravel(), minlength=logits.shape[-1])
    return float(sunk), float(np.abs(loads / loads.mean() - 1).max())


_ROUTER_CACHE = {}


def calibrated_routers(cfg: dict, seed: int, params) -> dict:
    """{layer index: router kernel [H, E]}, layer after layer on one
    seeded batch of the traffic's shape (``calibration_batch`` x
    ``calibration_seq_len`` ids, corrupted and laid out as a step lays
    them out). A checkpoint trained with its balancing loss spreads its
    rows over the experts; seeded weights as drawn do not (PR 30's cell:
    one held expert at 4.2 times the mean, differently on every seed),
    and this family has no selection bias to calibrate, so the seeded
    WEIGHTS change. What they can be made to do is less than balance: the
    rows that hold the mask id reach the router as one point (identical
    embeddings; what attention adds under seeded weights is small and is
    drawn anew by every batch), so all of them take the same
    ``num_experts_per_tok`` experts, each of which gets a quarter of all
    rows whatever the router's weights. The calibration decides WHICH
    experts (``sink_experts``: one held, the others absent, so that the
    chip's part of the assignments is the deployment's) and by what margin
    (``router_sink_margin``, which the router's drift within a run does
    not use up): ``sunk_router``. The other rows, whose embeddings all
    differ, spread evenly by themselves; every expert's load over them
    has to lie within ``router_tolerance`` of the mean, and all but a
    hundredth of the mask id's rows have to take the sinks, or this
    raises."""
    key = (json.dumps(cfg, sort_keys=True), seed)
    if key in _ROUTER_CACHE:
        return _ROUTER_CACHE[key]
    rng = np.random.default_rng([seed % (2 ** 31), 0xCA11B])
    rows, seq = int(cfg["calibration_batch"]), int(cfg["calibration_seq_len"])
    ids = jnp.asarray(rng.integers(0, cfg["mask_token_id"], (rows, seq),
                                   dtype=np.int32))
    xt, m, _ = corrupt(ids, jax.random.PRNGKey(seed % (2 ** 31)), cfg)
    masked = jnp.concatenate([m, jnp.zeros_like(m)], axis=1)
    k, held = int(cfg["num_experts_per_tok"]), held_experts(cfg)
    p = params["decoder"]
    x = jax.jit(lambda table: table[jnp.concatenate([xt, ids], 1)])(
        p["embed"]["embedding"])
    attend = jax.jit(lambda block, x: _attend(block, x, cfg))
    experts = jax.jit(lambda block, h, z: h + _moe(block["moe"], z, cfg))
    logits = jax.jit(lambda z, kernel: jnp.matmul(
        z.reshape(-1, z.shape[-1]), kernel, precision=HIGHEST))
    kernels, off = {}, {}
    for i in range(cfg["num_hidden_layers"]):
        block = p[f"block_{i}"]
        h, z = attend(block, x)
        centre, others = _row_means(z, masked)
        drawn = block["moe"]["router"]["kernel"]
        sinks = sink_experts(jnp.matmul(centre, drawn, precision=HIGHEST),
                             held, k)
        kernels[i] = sunk_router(drawn, centre, others, sinks,
                                 float(cfg["router_sink_margin"]))
        sunk, worst = routing_loads(logits(z, kernels[i]), masked, k, sinks)
        if sunk < 0.99 or worst > float(cfg["router_tolerance"]):
            off[i] = (sunk, worst)
        block = dict(block, moe=dict(block["moe"],
                                     router={"kernel": kernels[i]}))
        x = experts(block, h, z)
    if off:
        raise RuntimeError(
            f"router: (share of the mask id's rows on the sinks, worst "
            f"load error over the other rows) outside 0.99 / "
            f"{cfg['router_tolerance']}: {off}")
    _ROUTER_CACHE[key] = kernels
    return kernels


def make_params(cfg: dict, seed: int):
    """The seeded weights with each layer's router calibrated
    (``calibrated_routers``), as float32 numpy arrays: made on the device
    in one jitted call and fetched, so that neither side keeps a second
    copy of 2 GB there (the reference follows three Adam steps beside
    them)."""
    params = draw_weights(cfg, seed)
    for i, kernel in calibrated_routers(cfg, seed, params).items():
        params["decoder"][f"block_{i}"]["moe"]["router"]["kernel"] = kernel
    return jax.tree_util.tree_map(np.asarray, params)


# ------------------------------------------------------------ corruption

def fold_static(key, data):
    """flax.core.scope._fold_in_static, restated: fold a tuple of strings
    and ints into a key through the first four bytes of its SHA-1."""
    m = hashlib.sha1()
    for x in data:
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(x.to_bytes((x.bit_length() + 7) // 8, byteorder="big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], byteorder="big")))


def corrupt(ids, key, cfg: dict):
    """``(xt, m, t)`` of the whole batch ``ids`` [b, L] from the step's
    key, as the program draws them: the key is the first one the top
    module asks of its ``dropout`` stream (``fold_static(key, (1,))``),
    split in two, the first half for ``u`` [b, K], the second for one
    uniform a position, masked where it falls under its block's ``t``."""
    b, L = ids.shape
    B, eps = int(cfg["block_length"]), float(cfg["noise_eps"])
    key_t, key_m = jax.random.split(fold_static(key, (1,)))
    u = jax.random.uniform(key_t, (b, L // B), jnp.float32)
    t = (1.0 - eps) * u + eps
    m = jax.random.uniform(key_m, (b, L), jnp.float32) \
        < jnp.repeat(t, B, axis=1)
    return jnp.where(m, jnp.int32(cfg["mask_token_id"]), ids), m, t


def allowed_pairs(rows, L: int, B: int, fault: str = None):
    """The mask's rows ``rows`` (indices into the 2L input) as a boolean
    ``[len(rows), 2L]``, from the five lines of the module docstring.
    ``fault="own_clean_block"``: the noisy half also sees the clean keys
    of its OWN block (the answer leaks)."""
    i, j = rows[:, None], jnp.arange(2 * L)[None, :]
    q_noisy, k_noisy = i < L, j < L
    bi, bj = (i % L) // B, (j % L) // B
    before = (bj <= bi) if fault == "own_clean_block" else (bj < bi)
    noisy_row = (k_noisy & (bj == bi)) | (~k_noisy & before)
    clean_row = ~k_noisy & (bj <= bi)
    return jnp.where(q_noisy, noisy_row, clean_row)


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


def _rope(x, positions, theta):
    """x [b, n, heads, d] at ``positions`` [n]: ``x * cos + rotate_half(x)
    * sin``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def _attention(p, x, cfg, quant, fault):
    """x [b, 2L, H], the noisy half first."""
    b, n, _ = x.shape
    L, B = n // 2, int(cfg["block_length"])
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    group = h // g
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    positions = jnp.arange(n) % L
    q = _mm(x, p["q"]["kernel"], quant).reshape(b, n, h, d)
    k = _mm(x, p["k"]["kernel"], quant).reshape(b, n, g, d)
    v = _mm(x, p["v"]["kernel"], quant).reshape(b, n, g, d)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), positions, theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), positions, theta)
    chunk = min(n, QUERY_CHUNK)
    # [kv head, chunk of queries, b, chunk, group, d]
    qs = q.reshape(b, n // chunk, chunk, g, group, d) \
        .transpose(3, 1, 0, 2, 4, 5)
    ks, vs = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)   # [g,b,n,d]

    @jax.checkpoint
    def one(qc, kg, vg, first):
        """One chunk of query rows of the query heads one key-value head
        serves, against all its keys, under the dense mask's rows."""
        if quant is not None:
            qc, kg = quant(qc, -1), quant(kg, -1)
        scores = jnp.einsum("bqnd,bkd->bnqk", qc, kg, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(d))
        seen = allowed_pairs(first + jnp.arange(chunk), L, B, fault)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        if quant is not None:
            probs, vg = quant(probs, -1), quant(vg, -2)
        return jnp.einsum("bnqk,bkd->bqnd", probs, vg, precision=HIGHEST)

    def head(args):
        qg, kg, vg = args
        firsts = jnp.arange(n // chunk) * chunk
        return jax.lax.map(lambda a: one(a[0], kg, vg, a[1]), (qg, firsts))

    ctx = jax.lax.map(head, (qs, ks, vs))   # [g, chunks, b, chunk, group, d]
    ctx = ctx.transpose(2, 1, 3, 0, 4, 5).reshape(b, n, h * d)
    return _mm(ctx, p["out"]["kernel"], quant)


def _gated(x, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def _moe(p, z, cfg, quant=None):
    """This chip's share: every held expert over every token, masked by
    the token's weight for it (zero where it did not select it)."""
    k = int(cfg["num_experts_per_tok"])
    probs = jax.nn.softmax(_mm(z, p["router"]["kernel"], quant), axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

    @jax.checkpoint
    def one(expert):
        e, w1, w3, w2 = expert
        mine = jnp.sum(jnp.where(ids == e, weights, 0.0), -1, keepdims=True)
        return mine * _gated(z, w1, w3, w2, quant)

    # the running sum stays outside the checkpoint: an addition keeps
    # nothing for the backward pass, so no sum is held for every expert
    E = p["experts"]
    total, _ = jax.lax.scan(
        lambda total, expert: (total + one(expert), None), jnp.zeros_like(z),
        (jnp.asarray(held_experts(cfg), jnp.int32), E["w1"], E["w3"],
         E["w2"]))
    return total


def _attend(block, x, cfg, quant=None, fault=None):
    """(h, RMSNorm_ffn(h)) with ``h = x + attention(RMSNorm_op(x))``."""
    eps = cfg["rms_norm_eps"]
    y = _rms(x, block["operator_norm"]["scale"], eps)
    h = x + _attention(block["attention"], y, cfg, quant, fault)
    return h, _rms(h, block["ffn_norm"]["scale"], eps)


def layer(block, x, cfg: dict, quant=None, fault=None):
    """One layer over rows ``x`` [rows, 2L, H]."""
    h, z = _attend(block, x, cfg, quant, fault)
    return h + _moe(block["moe"], z, cfg, quant)


def hidden(params, xt, x0, cfg: dict, quant=None, fault=None):
    """The stack's output [rows, L, H] over the NOISY half of ``[xt ;
    x0]``, before the last norm; every layer recomputed in the backward
    pass."""
    p = params["decoder"]
    x = p["embed"]["embedding"][jnp.concatenate([xt, x0], axis=1)]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda block, x: layer(block, x, cfg, quant, fault))(
                p[f"block_{i}"], x)
    return x[:, :x0.shape[1]]


def head(params, x, cfg: dict, quant=None):
    """Float32 logits of rows ``x`` [..., H]: the last norm, the untied
    head."""
    p = params["decoder"]
    x = _rms(x, p["out_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(x, p["lm_head"]["kernel"], quant)


def forward(params, xt, x0, cfg: dict, quant=None, fault=None):
    """Float32 logits [rows, L, vocab] of the NOISY half of ``[xt ;
    x0]``."""
    return head(params, hidden(params, xt, x0, cfg, quant, fault), cfg,
                quant)


# -------------------------------------------------------------- training

def loss_sum(params, xt, x0, weights, cfg, quant=None, fault=None):
    """``sum weights * nll`` of a block of rows: ``weights`` [rows, L] is
    ``m / t``, the nll that of each noisy position's logits for its own
    clean id."""
    return head_loss(params, hidden(params, xt, x0, cfg, quant, fault), x0,
                     weights, cfg, quant)


def head_loss(params, x, x0, weights, cfg, quant=None):
    """``sum weights * nll`` from the stack's output ``x`` [rows, L, H]."""
    rows, L, H = x.shape
    chunk = min(L, HEAD_CHUNK)

    @jax.checkpoint
    def one(total, part):
        """A chunk of positions: their float32 logits exist for one chunk
        at a time, in the backward pass too."""
        x, x0, weights = part
        logp = jax.nn.log_softmax(head(params, x, cfg, quant), axis=-1)
        nll = -jnp.take_along_axis(logp, x0[..., None], axis=-1)[..., 0]
        return total + jnp.sum(weights * nll), None

    def chunks(a):
        return jnp.moveaxis(a.reshape(rows, L // chunk, chunk, *a.shape[2:]),
                            1, 0)

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32),
                            (chunks(x), chunks(x0), chunks(weights)))
    return total


def make_loss_and_grad(cfg, batch: int, block: int, quant=None, used=None,
                       fault=None):
    """(params, ids [batch, L], labels [batch, L], key) -> (the loss of
    the module docstring, gradient of it as float32 numpy arrays),
    accumulated on the host over blocks of ``block`` rows, so that the
    Adam steps the driver follows fit beside it. ``key`` is the step's
    dropout key: the whole batch is corrupted from it as the program
    corrupts it (``corrupt``), and a block takes its rows. ``labels`` are
    the ids themselves. ``used`` (default: all) plants a fault for the
    tests and the fault readings: only the first ``used`` rows count, the
    mean taken over them; a batch of one row has no half, so ``used=0``
    there leaves out the second half of the row's POSITIONS and takes the
    mean over the rest. ``fault``: one of ``MASK_FAULTS``."""
    used = batch if used is None else used
    half_row = used == 0
    if half_row:
        used = batch
    block = max(1, min(block, used))
    if used % block:
        raise ValueError(f"{used} rows do not divide into blocks of {block}")
    if fault not in (None,) + MASK_FAULTS:
        raise ValueError(f"fault {fault!r} is not one of {MASK_FAULTS}")

    # The gradient is taken a layer at a time, each layer's rule a
    # program of its own (all layers share one): the forward pass keeps
    # every layer's input, the head gives the loss and the cotangent of
    # the stack's output, and the layers are gone through backwards, each
    # recomputed inside its rule, its parameters' gradient leaving the
    # device before the next rule runs. One program over the whole stack
    # takes 12 GB beside the parameters and Adam's moments at the cell's
    # size; a layer's rule takes 4
    forward_layer = jax.jit(lambda block, x: layer(block, x, cfg, quant,
                                                   fault))

    @jax.jit
    def backward_layer(block, x, g):
        _, rule = jax.vjp(lambda block, x: layer(block, x, cfg, quant,
                                                 fault), block, x)
        return rule(g)

    @jax.jit
    def embed(table, ids):
        return table[ids]

    @jax.jit
    def backward_embed(table, ids, g):
        return jnp.zeros_like(table).at[ids].add(g)

    @jax.jit
    def backward_head(top, x, x0, weights):
        """(loss sum, gradient of out_norm and lm_head, cotangent of the
        stack's whole output: zero over the clean half)."""
        seq = x0.shape[1]
        loss, (g_top, g_x) = jax.value_and_grad(
            lambda top, x: head_loss({"decoder": top}, x, x0, weights, cfg,
                                     quant), argnums=(0, 1))(top, x[:, :seq])
        return loss, g_top, jnp.pad(g_x, ((0, 0), (0, seq), (0, 0)))

    def to_host(tree, scale):
        return jax.tree_util.tree_map(lambda g: np.asarray(g) * scale, tree)

    def block_grad(p, xt, x0, weights, scale):
        """(loss sum, the block's gradient on the host, scaled)."""
        ids = jnp.concatenate([xt, x0], axis=1)
        n = cfg["num_hidden_layers"]
        xs = [embed(p["embed"]["embedding"], ids)]
        for i in range(n):
            xs.append(forward_layer(p[f"block_{i}"], xs[-1]))
        top = {k: p[k] for k in ("out_norm", "lm_head")}
        loss, g_top, g = backward_head(top, xs.pop(), x0, weights)
        grads = to_host(g_top, scale)
        for i in reversed(range(n)):
            g_block, g = backward_layer(p[f"block_{i}"], xs.pop(), g)
            grads[f"block_{i}"] = to_host(g_block, scale)
            del g_block
        grads["embed"] = to_host(
            {"embedding": backward_embed(p["embed"]["embedding"], ids, g)},
            scale)
        return loss, grads

    def loss_and_grad(params, ids, labels, key):
        p = jax.tree_util.tree_map(jnp.asarray, params)["decoder"]
        x0 = jnp.asarray(labels)
        xt, m, t = corrupt(jnp.asarray(ids), key, cfg)
        weights = m / jnp.repeat(t, int(cfg["block_length"]), axis=1)
        counted = x0.shape[1] // 2 if half_row else x0.shape[1]
        weights = weights * (jnp.arange(x0.shape[1]) < counted)
        scale = np.float32(1.0 / (used * counted))
        total, grads = 0.0, None
        for start in range(0, used, block):
            rows = slice(start, start + block)
            loss, g = block_grad(p, xt[rows], x0[rows], weights[rows], scale)
            total += float(loss)
            grads = g if grads is None else jax.tree_util.tree_map(
                np.add, grads, g)
        return total * float(scale), {"decoder": grads}

    return loss_and_grad
