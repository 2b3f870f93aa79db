"""Kimi delta attention (KDA, arXiv:2510.26692): the gated delta rule with
a decay per key channel, computed a chunk of positions at a time.

Per head, with ``q_t``, ``k_t`` of ``d_k`` channels (L2-normalised),
``v_t`` of ``d_v``, a gate ``β_t`` in (0, 1) and a log-decay ``g_t`` < 0
per key channel:

    S_t = (I - β_t k_t k_tᵀ) Diag(exp g_t) S_{t-1} + β_t k_t v_tᵀ,  S_0 = 0
    o_t = S_tᵀ q_t · scale

With a scalar in place of ``g_t`` this is the gated delta rule of
``transformers``' ``qwen3_next`` (``torch_recurrent_gated_delta_rule``).

The chunked form (the WY / UT representation, as the KDA paper and fla's
``chunk_kda`` write it). Inside a chunk of ``C`` positions from a state
``S``, with ``G_t`` the sum of ``g`` over the chunk's positions up to and
including ``t``:

    u_t = β_t (v_t - (Diag(exp g_t) S_{t-1})ᵀ k_t)      so  S_t = D_t S_{t-1} + k_t u_tᵀ
    (I + A) U = Diag(β) (V - (K ⊙ exp G) S),  A_ti = β_t Σ_c k_tc k_ic e^(G_tc - G_ic), i < t
    O = (Q ⊙ exp G) S + P U,                  P_ti = Σ_c q_tc k_ic e^(G_tc - G_ic),  i <= t
    S' = exp(G_C) ⊙ S + (K ⊙ exp(G_C - G))ᵀ U

``A``, ``P``, the inverse of ``I + A`` and its products with ``V`` and
``K ⊙ exp G`` hold no state and are computed for every chunk at once
(``_intra``); one pass over the chunks carries the state
(``_inter_step``).
A decay is only ever a ratio ``exp(G_t - G_i)`` with ``t`` at or after
``i``: with ``g`` down to -5 a position, ``exp(-5 · 64)`` underflows
float32, so ``exp(G_t)`` and ``exp(-G_i)`` are never formed apart. The
pairs of ``A`` and ``P`` are products on sub-chunks of ``SUB`` positions:
the pairs of row ``t`` with the keys of sub-chunk ``b`` take the decay
from ``t`` to ``b``'s last position on the row's side and from ``i`` to
that position on the key's side. Both factors are at most 1 where
``t`` lies after ``b``, and within ``b`` the row's factor is at most
``exp(5 · (SUB - 1))``, which float32 holds; a factor that underflows
belongs to a pair whose decay does too.

The backward pass (``custom_vjp``) keeps the state at the start of each
chunk and nothing per position: it recomputes the chunks' products, runs
the state's pass backward, and differentiates the products through
``jax.vjp``. Everything runs in float32 at the highest matmul precision
under the scope ``scan``. The states and the rule's output go through
``checkpoint_name`` (``RESIDUAL_NAMES``): a ``jax.checkpoint`` whose
policy keeps them runs the pass over the chunks once a step and
recomputes the rest of the layer.
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.ops.short_conv import causal_depthwise_conv

#: positions a chunk: the state is carried once a chunk
CHUNK = 64
#: positions of a sub-chunk, the unit of the decays' factors (module
#: docstring)
SUB = 16
#: the floor of the gate's log-decay a position (fla's ``kda_safe_gate``
#: with ``lower_bound``)
LOWER_BOUND = -5.0

HIGHEST = jax.lax.Precision.HIGHEST

#: the names of what the backward pass needs of the pass over the chunks:
#: the state at each chunk's start (``d_k x d_v`` a head a chunk) and the
#: rule's output
RESIDUAL_NAMES = ("kda_states", "kda_out")


def _mm(spec, *arrays):
    return jnp.einsum(spec, *arrays, precision=HIGHEST)


def _chunks(x, chunk: int):
    """[b, t, h, ...] -> [b, h, n, chunk, ...] float32, zero-padded to
    whole chunks: a padded position has no key, value or gate and leaves
    the state as it is (``g`` 0)."""
    b, t = x.shape[:2]
    n = -(-t // chunk)
    x = jnp.pad(x.astype(jnp.float32),
                [(0, 0), (0, n * chunk - t)] + [(0, 0)] * (x.ndim - 2))
    x = x.reshape(b, n, chunk, *x.shape[2:])
    return jnp.moveaxis(x, 3, 1)


def _unchunk(x, t: int):
    """[b, h, n, chunk, d] -> [b, t, h, d]."""
    b, h, n, c, d = x.shape
    return jnp.moveaxis(x, 1, 3).reshape(b, n * c, h, d)[:, :t]


def _decayed_pairs(a, key, G, sub: int):
    """``Z_ti = Σ_c a_tc key_ic exp(G_tc - G_ic)`` over one chunk's rows
    ``a`` [.., C, d] and keys ``key`` [.., C, d], for every pair (the
    caller masks): products on sub-chunks of ``sub`` keys, each decay cut
    at the sub-chunk's last position (module docstring)."""
    C, d = a.shape[-2:]
    nb = C // sub
    ref = G[..., sub - 1::sub, :]                          # [.., nb, d]
    # the row's factor towards each sub-chunk's reference, where the row
    # lies at or after the sub-chunk's start (exponent 0 elsewhere: those
    # pairs are masked)
    live = (jnp.arange(C)[None, :] // sub) >= jnp.arange(nb)[:, None]
    rows = jnp.exp(jnp.where(live[:, :, None],
                             G[..., None, :, :] - ref[..., :, None, :], 0.))
    keys = key.reshape(*key.shape[:-2], nb, sub, d) * jnp.exp(
        ref[..., :, None, :] - G.reshape(*G.shape[:-2], nb, sub, d))
    z = _mm("...btd,...bsd->...tbs", a[..., None, :, :] * rows, keys)
    return z.reshape(*z.shape[:-2], C)


def _unit_lower_inverse(A, sub: int):
    """``(I + A)^-1`` for a strictly lower ``A`` [.., C, C]: each diagonal
    block of ``sub`` rows inverted by forward substitution, a row at a
    time, then the block rows in turn, ``T_r = D_r^-1 (E_r - sum_{m < r}
    A_rm T_m)``: matrix products and ``sub + C / sub`` steps, all of them
    XLA's on every backend (LAPACK's solve on the CPU runs on the BLAS
    library's own threads beside the devices' collectives)."""
    C = A.shape[-1]
    nb = C // sub
    blocks = jnp.stack([A[..., r * sub:(r + 1) * sub, r * sub:(r + 1) * sub]
                        for r in range(nb)], -3)          # [.., nb, sub, sub]
    inv = jnp.broadcast_to(jnp.eye(sub, dtype=A.dtype), blocks.shape)
    for t in range(1, sub):
        row = inv[..., t, :] - _mm("...j,...jk->...k", blocks[..., t, :t],
                                   inv[..., :t, :])
        inv = inv.at[..., t, :].set(row)
    rows = []
    eye = jnp.eye(C, dtype=A.dtype)
    for r in range(nb):
        rhs = jnp.broadcast_to(eye[r * sub:(r + 1) * sub],
                               A.shape[:-2] + (sub, C))
        for m in range(r):
            rhs = rhs - _mm("...ij,...jk->...ik",
                            A[..., r * sub:(r + 1) * sub,
                              m * sub:(m + 1) * sub], rows[m])
        rows.append(_mm("...ij,...jk->...ik", inv[..., r, :, :], rhs))
    return jnp.concatenate(rows, -2)


def _intra(q, k, v, g, beta, sub: int):
    """What a chunk computes without the state, for every chunk at once:
    ``Wv``, ``Wk`` with ``U = Wv - Wk S``; ``P``; ``Qd = Q ⊙ exp G``;
    ``Kd = K ⊙ exp(G_C - G)``; ``decay = exp(G_C)``. Operands
    [b, h, n, C, ·] float32 (``beta`` [b, h, n, C])."""
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-2)
    t = jnp.arange(C)
    below = t[:, None] > t[None, :]
    A = jnp.where(below, beta[..., :, None] * _decayed_pairs(k, k, G, sub),
                  0.)
    P = jnp.where(t[:, None] >= t[None, :], _decayed_pairs(q, k, G, sub), 0.)
    W = _mm("...ij,...jd->...id", _unit_lower_inverse(A, sub),
            beta[..., None] * jnp.concatenate([v, k * jnp.exp(G)], -1))
    dv = v.shape[-1]
    last = G[..., -1:, :]
    return (W[..., :dv], W[..., dv:], P, q * jnp.exp(G),
            k * jnp.exp(last - G), jnp.exp(last[..., 0, :]))


def _inter_step(S, chunk):
    """One chunk's pass with the state ``S`` [b, h, d_k, d_v] at its start:
    (the state after it, the chunk's output)."""
    Wv, Wk, P, Qd, Kd, decay = chunk
    U = Wv - _mm("bhck,bhkv->bhcv", Wk, S)
    O = _mm("bhck,bhkv->bhcv", Qd, S) + _mm("bhct,bhtv->bhcv", P, U)
    S_next = decay[..., :, None] * S + _mm("bhck,bhcv->bhkv", Kd, U)
    return S_next, O


def _by_chunk(tree):
    """[b, h, n, ...] leaves -> [n, b, h, ...]: the scan's leading axis."""
    return jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 2, 0), tree)


def _forward(q, k, v, g, beta, chunk: int):
    t = q.shape[1]
    sub = min(SUB, chunk)
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of {sub}")
    ops = [_chunks(a, chunk) for a in (q, k, v, g)]
    ops.append(_chunks(beta[..., None], chunk)[..., 0])
    parts = _intra(*ops, sub)
    b, h, _, _, dk = ops[0].shape
    # zeros made from an operand: the state takes the operands' sharding
    # (a constant would be replicated, and a batch-sharded pass would
    # then gather the state across devices every chunk)
    S0 = jnp.broadcast_to(0. * ops[4][:, :, :1, :1],
                          (b, h, dk, v.shape[-1]))

    def step(S, chunk_parts):
        S_next, O = _inter_step(S, chunk_parts)
        return S_next, (O, S)

    _, (O, starts) = jax.lax.scan(step, S0, _by_chunk(parts))
    return (_unchunk(jnp.moveaxis(O, 0, 2), t),
            (ops, checkpoint_name(starts, RESIDUAL_NAMES[0])))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked(q, k, v, g, beta, chunk):
    return _forward(q, k, v, g, beta, chunk)[0]


def _chunked_fwd(q, k, v, g, beta, chunk):
    return _forward(q, k, v, g, beta, chunk)


def _chunked_bwd(chunk, res, d_out):
    ops, starts = res
    t = d_out.shape[1]
    sub = min(SUB, chunk)
    parts, intra_vjp = jax.vjp(functools.partial(_intra, sub=sub), *ops)
    dO = _chunks(d_out, chunk)

    def step(dS, xs):
        (Wv, Wk, P, Qd, Kd, decay), S, dOc = xs
        U = Wv - _mm("bhck,bhkv->bhcv", Wk, S)
        dU = _mm("bhct,bhcv->bhtv", P, dOc) + _mm("bhck,bhkv->bhcv", Kd, dS)
        dS_prev = (_mm("bhck,bhcv->bhkv", Qd, dOc) + decay[..., :, None] * dS
                   - _mm("bhck,bhcv->bhkv", Wk, dU))
        grads = (dU, -_mm("bhcv,bhkv->bhck", dU, S),
                 _mm("bhcv,bhtv->bhct", dOc, U),
                 _mm("bhcv,bhkv->bhck", dOc, S),
                 _mm("bhcv,bhkv->bhck", U, dS),
                 jnp.sum(S * dS, -1))
        return dS_prev, grads

    dS_end = jnp.zeros_like(starts[0])
    _, grads = jax.lax.scan(step, dS_end, (_by_chunk(parts), starts,
                                           _by_chunk(dO)), reverse=True)
    grads = jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 0, 2), grads)
    dq, dk, dv, dg, dbeta = intra_vjp(grads)
    out = [_unchunk(a, t) for a in (dq, dk, dv, dg)]
    out.append(_unchunk(dbeta[..., None], t)[..., 0])
    return tuple(out)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def chunked_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """``o_t = S_tᵀ q_t / sqrt(d_k)`` of the gated delta rule with a decay
    per key channel (module docstring), float32. ``q``, ``k`` [b, t, h,
    d_k] (normalised by the caller), ``v`` [b, t, h, d_v], ``g`` [b, t, h,
    d_k] the log-decay (<= 0), ``beta`` [b, t, h]. ``t`` need not be a
    multiple of ``chunk``."""
    with jax.named_scope("scan"):
        q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
        return checkpoint_name(
            _chunked(q * q.shape[-1] ** -0.5, k, v, g, beta, chunk),
            RESIDUAL_NAMES[1])


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32 (fla's
    ``l2norm``)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _log_uniform_a(key, shape, dtype=jnp.float32):
    """fla's ``A_log``: log of U(1, 16)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class KimiDeltaAttention(nn.Module):
    """``x`` [batch, seq, hidden] -> [batch, seq, hidden] through the
    ``num_heads`` heads this layer holds (the chip's share of a layer
    whose heads lie on several chips: the shares of a partition of the
    heads add up to the whole layer). No positions, no bias. Per token:

        q, k, v = SiLU(conv(W_{q,k,v} x))      (causal depthwise, ``conv_taps``)
        q, k = L2-normalised per head;  β = σ(W_b x) per head
        g = LOWER_BOUND · σ(exp(A_log) ⊙ (W_f x + dt_bias)) per key channel
        o = chunked_delta_rule(q, k, v, g, β)
        y = W_o [RMSNorm_head(o) ⊙ σ(W_g x)]

    The gate's form is fla's ``kda_safe_gate`` (``LOWER_BOUND``);
    ``A_log`` is one number a head, ``dt_bias``
    one a key channel; the output norm has one scale of ``head_dim``
    shared by the heads (one head a group)."""

    num_heads: int
    head_dim: int
    conv_taps: int = 4
    norm_eps: float = 1e-6
    dtype: Optional[object] = None
    kernel_init: nn.initializers.Initializer = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        h, d = self.num_heads, self.head_dim

        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=self.kernel_init, name=name)(x)

        def conv(name):
            y = dense(name, h * d)
            taps = self.param(f"{name}_conv", self.kernel_init,
                              (self.conv_taps, h * d), jnp.float32)
            y = nn.silu(causal_depthwise_conv(y, taps.astype(y.dtype)))
            return y.reshape(b, s, h, d)

        q, k, v = conv("q"), conv("k"), conv("v")
        q, k = l2_normalize(q), l2_normalize(k)
        beta = jax.nn.sigmoid(dense("b", h).astype(jnp.float32))
        a_log = self.param("A_log", _log_uniform_a, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h * d,),
                             jnp.float32)
        f = (dense("f", h * d).astype(jnp.float32) + dt_bias).reshape(
            b, s, h, d)
        g = LOWER_BOUND * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * f)
        o = chunked_delta_rule(q, k, v, g, beta)
        o = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                       name="o_norm")(o)
        gate = jax.nn.sigmoid(dense("g", h * d))
        y = (o.reshape(b, s, h * d) * gate).astype(gate.dtype)
        return nn.Dense(hidden, use_bias=False, dtype=self.dtype,
                        kernel_init=self.kernel_init, name="o")(y)
