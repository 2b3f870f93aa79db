"""Loss functions (objectives) — ref zoo Keras objectives
(``pyzoo/zoo/pipeline/api/keras/objectives.py`` lowering to BigDL criterions).

Every loss is ``fn(y_true, y_pred) -> per-sample loss [batch]`` so the train
step can apply padding masks before reduction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-7


def _flatten_trailing(a):
    a = jnp.asarray(a)
    return a.reshape(a.shape[0], -1) if a.ndim > 1 else a[:, None]


def mean_squared_error(y_true, y_pred):
    y_pred = _f32(y_pred)
    return jnp.square(_flatten_trailing(y_pred) - _flatten_trailing(y_true)).mean(-1)


def mean_absolute_error(y_true, y_pred):
    y_pred = _f32(y_pred)
    return jnp.abs(_flatten_trailing(y_pred) - _flatten_trailing(y_true)).mean(-1)


def mean_absolute_percentage_error(y_true, y_pred):
    y_pred, y_true = _f32(y_pred), _f32(y_true)
    t = _flatten_trailing(y_true)
    return (100.0 * jnp.abs((t - _flatten_trailing(y_pred))
                            / jnp.clip(jnp.abs(t), _EPS, None))).mean(-1)


def mean_squared_logarithmic_error(y_true, y_pred):
    y_pred, y_true = _f32(y_pred), _f32(y_true)
    a = jnp.log1p(jnp.clip(_flatten_trailing(y_pred), _EPS, None))
    b = jnp.log1p(jnp.clip(_flatten_trailing(y_true), _EPS, None))
    return jnp.square(a - b).mean(-1)


def _f32(a):
    """Losses compute in fp32 even under a bf16 compute policy: log/exp/
    square/divide of bf16 values costs accuracy for no MXU win (the loss
    is a scalar tail, not a matmul). Applied to predictions everywhere,
    and ALSO to targets wherever the target enters a nonlinear op (the
    log/ratio family: msle, mape, kld, poisson) — a bf16 target inside a
    log would otherwise evaluate the transcendental at bf16 precision
    even though everything around it is fp32."""
    a = jnp.asarray(a)
    return a.astype(jnp.float32) \
        if jnp.issubdtype(a.dtype, jnp.floating) else a


def binary_crossentropy(y_true, y_pred):
    y_pred = _f32(y_pred)
    p = jnp.clip(_flatten_trailing(y_pred), _EPS, 1 - _EPS)
    t = _flatten_trailing(y_true)
    return -(t * jnp.log(p) + (1 - t) * jnp.log1p(-p)).mean(-1)


def binary_crossentropy_from_logits(y_true, y_pred):
    y_pred = _f32(y_pred)
    z = _flatten_trailing(y_pred)
    t = _flatten_trailing(y_true)
    return (jnp.maximum(z, 0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))).mean(-1)


def categorical_crossentropy(y_true, y_pred):
    y_pred = _f32(y_pred)
    p = jnp.clip(y_pred, _EPS, 1.0)
    return -(y_true * jnp.log(p)).sum(-1)


def sparse_categorical_crossentropy(y_true, y_pred):
    y_pred = _f32(y_pred)
    logp = jnp.log(jnp.clip(y_pred, _EPS, 1.0))
    idx = jnp.asarray(y_true).astype(jnp.int32)
    return -jnp.take_along_axis(logp, idx[..., None], axis=-1)[..., 0]


#: float32 bytes of ``[..., vocab]`` logits beyond which their float32
#: copy is taken a block of positions at a time (a language model's
#: ``[batch, seq, vocab]``: 1 GB at 16,384 tokens x 16,384 ids)
LOGITS_BLOCK_BYTES = 256 << 20


def _token_nll(logits, idx):
    logits = _f32(logits)
    logp = logits - jax_logsumexp(logits)
    return -jnp.take_along_axis(logp, idx[..., None], axis=-1)[..., 0]


def _token_nll_in_blocks(logits, idx):
    """``_token_nll`` over blocks of positions, each recomputed in the
    backward pass, so that the float32 logits, their softmax and its
    gradient exist for one block at a time."""
    vocab = logits.shape[-1]
    positions = idx.size
    blocks = next((n for n in range(1, positions + 1)
                   if positions % n == 0
                   and positions // n * vocab * 4 <= LOGITS_BLOCK_BYTES),
                  None)
    if blocks in (None, 1):
        return _token_nll(logits, idx)
    out = jax.lax.map(
        jax.checkpoint(lambda block: _token_nll(*block)),
        (logits.reshape(blocks, -1, vocab), idx.reshape(blocks, -1)))
    return out.reshape(idx.shape)


def _token_nll_of(y_true, logits):
    """``_token_nll``, in blocks of positions where the float32 copy of
    ``[batch, seq, vocab]`` logits would pass ``LOGITS_BLOCK_BYTES``."""
    idx = jnp.asarray(y_true).astype(jnp.int32)
    logits = jnp.asarray(logits)
    if logits.ndim > 2 and logits.size * 4 > LOGITS_BLOCK_BYTES:
        return _token_nll_in_blocks(logits, idx)
    return _token_nll(logits, idx)


def sparse_categorical_crossentropy_from_logits(y_true, y_pred):
    out = _token_nll_of(y_true, y_pred)
    if out.ndim > 1:  # e.g. seq models: mean over time
        out = out.mean(axis=tuple(range(1, out.ndim)))
    return out


def weighted_sparse_categorical_crossentropy_from_logits(y_true, y_pred):
    """Token cross-entropy with a weight a position: ``y_pred`` is
    ``(logits [batch, seq, vocab], weights [batch, seq])`` and the loss of
    a row ``sum_i weights_i * nll_i / seq`` — block-diffusion training's
    ``1[masked] / t`` (text/block_diffusion.py). A bare array of logits
    (the same module outside training) weighs every position 1. The
    float32 copy of large logits is taken in blocks, as the unweighted
    loss takes it."""
    logits, weights = y_pred if isinstance(y_pred, (tuple, list)) \
        else (y_pred, None)
    nll = _token_nll_of(y_true, logits)
    if weights is not None:
        nll = nll * _f32(weights)
    return nll.mean(axis=tuple(range(1, nll.ndim)))


def jax_logsumexp(x):
    m = jnp.max(x, axis=-1, keepdims=True)
    return m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))


def hinge(y_true, y_pred):
    return jnp.maximum(1.0 - _flatten_trailing(y_true) * _flatten_trailing(y_pred),
                       0.0).mean(-1)


def squared_hinge(y_true, y_pred):
    return jnp.square(jnp.maximum(
        1.0 - _flatten_trailing(y_true) * _flatten_trailing(y_pred), 0.0)).mean(-1)


def kullback_leibler_divergence(y_true, y_pred):
    t = jnp.clip(_f32(y_true), _EPS, 1.0)
    p = jnp.clip(_f32(y_pred), _EPS, 1.0)
    return (t * jnp.log(t / p)).sum(-1)


def poisson(y_true, y_pred):
    y_pred, y_true = _f32(y_pred), _f32(y_true)
    return (_flatten_trailing(y_pred)
            - _flatten_trailing(y_true) * jnp.log(_flatten_trailing(y_pred) + _EPS)
            ).mean(-1)


def cosine_proximity(y_true, y_pred):
    t = _flatten_trailing(y_true)
    p = _flatten_trailing(y_pred)
    t = t / jnp.clip(jnp.linalg.norm(t, axis=-1, keepdims=True), _EPS, None)
    p = p / jnp.clip(jnp.linalg.norm(p, axis=-1, keepdims=True), _EPS, None)
    return -(t * p).sum(-1)


def huber(y_true, y_pred, delta: float = 1.0):
    err = _flatten_trailing(y_pred) - _flatten_trailing(y_true)
    abs_err = jnp.abs(err)
    quad = jnp.minimum(abs_err, delta)
    return (0.5 * quad ** 2 + delta * (abs_err - quad)).mean(-1)


_REGISTRY = {
    "mse": mean_squared_error, "mean_squared_error": mean_squared_error,
    "mae": mean_absolute_error, "mean_absolute_error": mean_absolute_error,
    "mape": mean_absolute_percentage_error,
    "msle": mean_squared_logarithmic_error,
    "binary_crossentropy": binary_crossentropy,
    "bce_logits": binary_crossentropy_from_logits,
    "categorical_crossentropy": categorical_crossentropy,
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy,
    "sparse_categorical_crossentropy_logits":
        sparse_categorical_crossentropy_from_logits,
    "weighted_sparse_categorical_crossentropy_logits":
        weighted_sparse_categorical_crossentropy_from_logits,
    "hinge": hinge, "squared_hinge": squared_hinge,
    "kld": kullback_leibler_divergence,
    "poisson": poisson,
    "cosine_proximity": cosine_proximity,
    "huber": huber,
}


def get(loss):
    if callable(loss):
        return loss
    if isinstance(loss, str):
        key = loss.lower()
        if key not in _REGISTRY:
            raise ValueError(f"unknown loss {loss!r}; known: {sorted(_REGISTRY)}")
        return _REGISTRY[key]
    raise TypeError(f"loss must be str or callable, got {type(loss)}")
