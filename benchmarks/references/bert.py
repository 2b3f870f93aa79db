"""Plain float32 reference of the BERT encoder with a classifier head.

Follows google-research/bert ``modeling.py``: word + position + token-type
embeddings, layer norm, dropout; ``num_hidden_layers`` post-LN blocks
(self-attention, output projection, dropout, add & norm, feed-forward with
exact gelu, dropout, add & norm); tanh pooler over the first position; a
dense head on the pooled output. Departure from the paper, stated because
the system under test makes it: no dropout on the attention probabilities
(the system drops the attention block's output only), and no dropout on the
pooled output before the head.

Nothing here imports the system under test. Everything is ``jax.numpy`` in
float32 at matmul precision ``highest``; the only structure borrowed from
outside is the *parameter layout* (names and shapes, ``param_shapes``) so
that one set of seeded weights feeds both sides, and flax's static key
folding (``fold_static``) so that both sides drop the same elements.

``quant`` is the hook for the lower-precision control: a function applied to
both operands of every matrix product. The cells' control is ``fp8_e4m3``.
"""

import hashlib

import jax
import jax.numpy as jnp

LN_EPS = 1e-12


# ------------------------------------------------------------ parameters

def param_shapes(cfg: dict, n_classes: int = 2) -> dict:
    """Names and shapes of every parameter, as a nested dict of tuples."""
    H, I = cfg["hidden_size"], cfg["intermediate_size"]
    h = cfg["num_attention_heads"]
    d = H // h
    norm = {"scale": (H,), "bias": (H,)}
    proj = {"kernel": (H, h, d), "bias": (h, d)}
    bert = {
        "word_embeddings": {"embedding": (cfg["vocab_size"], H)},
        "position_embeddings":
            {"embedding": (cfg["max_position_embeddings"], H)},
        "token_type_embeddings": {"embedding": (cfg["type_vocab_size"], H)},
        "embed_norm": dict(norm),
        "pooler": {"kernel": (H, H), "bias": (H,)},
    }
    for i in range(cfg["num_hidden_layers"]):
        bert[f"block_{i}"] = {
            "attention": {"query": dict(proj), "key": dict(proj),
                          "value": dict(proj),
                          "out": {"kernel": (h, d, H), "bias": (H,)}},
            "attn_norm": dict(norm),
            "intermediate": {"kernel": (H, I), "bias": (I,)},
            "output": {"kernel": (I, H), "bias": (H,)},
            "ffn_norm": dict(norm),
        }
    return {"bert": bert, "Dense_0": {"kernel": (H, n_classes),
                                      "bias": (n_classes,)}}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_params(cfg: dict, seed: int, n_classes: int = 2):
    """Every leaf from the seed, in float32, in ONE jitted call on the
    default device: normal(0, initializer_range) everywhere, layer-norm
    scales 1 + that, so no leaf is all zeros and every path carries
    gradient."""
    shapes = param_shapes(cfg, n_classes)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    std = float(cfg.get("initializer_range", 0.02))

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            v = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            if path[-1].key == "scale":
                v = v + 1.0
            out.append(v)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


# --------------------------------------------------------------- dropout

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


def _drop(x, key, site, rate, rows):
    """Inverted dropout with the mask a linen ``Dropout`` at module path
    ``site`` draws from ``key`` for the whole batch; ``rows`` (start, size,
    batch) takes this block's rows of it."""
    if key is None or rate <= 0.0:
        return x
    start, size, batch = rows
    keep = 1.0 - rate
    mask = jax.random.bernoulli(fold_static(key, site + (1,)), keep,
                                (batch,) + x.shape[1:])
    mask = jax.lax.dynamic_slice_in_dim(mask, start, size, axis=0)
    return jnp.where(mask, x / keep, 0.0)


# --------------------------------------------------------------- forward

def fp8_e4m3(x, axis):
    """Round to float8 e4m3 (three mantissa bits) and back, scaled so the
    tensor's largest magnitude sits at the format's largest (448)."""
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def forward(params, ids, cfg: dict, dropout_key=None, rows=None, quant=None):
    """Logits [rows, n_classes] in float32. ``dropout_key`` switches the
    training-mode dropout on; ``rows`` = (start, size, batch) when ``ids``
    is a block of a larger batch whose masks are drawn whole."""
    H = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    d = H // h
    p_hidden = float(cfg["hidden_dropout_prob"])
    b, L = ids.shape
    rows = rows or (0, b, b)

    def mm(a, w):
        """a [..., k] @ w [k, n] at full float32 precision."""
        if quant is not None:
            a, w = quant(a, -1), quant(w, 0)
        return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)

    P = params["bert"]
    x = (P["word_embeddings"]["embedding"][ids]
         + P["position_embeddings"]["embedding"][jnp.arange(L)][None]
         + P["token_type_embeddings"]["embedding"][jnp.zeros_like(ids)])
    x = _norm(x, P["embed_norm"])
    x = _drop(x, dropout_key, ("bert", "Dropout_0"), p_hidden, rows)
    for i in range(cfg["num_hidden_layers"]):
        B = P[f"block_{i}"]
        A = B["attention"]

        def heads(name):
            w = A[name]["kernel"].reshape(H, H)
            return (mm(x, w) + A[name]["bias"].reshape(H)).reshape(b, L, h, d)

        q, k, v = heads("query"), heads("key"), heads("value")
        qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if quant is not None:
            qh, kh = quant(qh, -1), quant(kh, -1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(scores / jnp.sqrt(jnp.float32(d)), axis=-1)
        if quant is not None:
            probs, vh = quant(probs, -1), quant(vh, -2)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, vh,
                         precision=jax.lax.Precision.HIGHEST)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, L, H)
        a = mm(ctx, A["out"]["kernel"].reshape(H, H)) + A["out"]["bias"]
        a = _drop(a, dropout_key, ("bert", f"block_{i}", "attention",
                                   "Dropout_0"), p_hidden, rows)
        x = _norm(x + a, B["attn_norm"])
        f = _gelu(mm(x, B["intermediate"]["kernel"])
                  + B["intermediate"]["bias"])
        f = mm(f, B["output"]["kernel"]) + B["output"]["bias"]
        f = _drop(f, dropout_key, ("bert", f"block_{i}", "Dropout_0"),
                  p_hidden, rows)
        x = _norm(x + f, B["ffn_norm"])
    pooled = jnp.tanh(mm(x[:, 0], P["pooler"]["kernel"])
                      + P["pooler"]["bias"])
    head = params["Dense_0"]
    return mm(pooled, head["kernel"]) + head["bias"]


# -------------------------------------------------------------- training

def loss_sum(params, ids, labels, cfg, dropout_key, rows, quant=None):
    """Summed softmax cross-entropy of a block of rows."""
    logits = forward(params, ids, cfg, dropout_key, rows, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def make_loss_and_grad(cfg, batch: int, block: int, quant=None, used=None):
    """(params, ids [batch, L], labels [batch], dropout_key) -> (mean loss,
    gradient of it), accumulated over blocks of ``block`` rows so that
    float32 activations of the whole batch never live at once. ``used``
    (default: all) plants a fault for the tests and the fault readings:
    only the first ``used`` rows count, the mean taken over them."""
    used = batch if used is None else used
    if used % block:
        raise ValueError(f"{used} rows do not divide into blocks of {block}")

    @jax.jit
    def block_grad(params, ids, labels, key, start):
        return jax.value_and_grad(loss_sum)(
            params, ids, labels, cfg, key, (start, block, batch), quant)

    def loss_and_grad(params, ids, labels, key):
        total, grads = 0.0, None
        for start in range(0, used, block):
            l, g = block_grad(params, ids[start:start + block],
                              labels[start:start + block], key,
                              jnp.int32(start))
            total = total + l
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        scale = 1.0 / used
        return total * scale, jax.tree_util.tree_map(
            lambda g: g * scale, grads)

    return loss_and_grad
