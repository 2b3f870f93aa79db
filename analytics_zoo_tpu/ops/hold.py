"""Elementwise residuals of the training step, computed once.

XLA's TPU compiler is free to throw a saved elementwise value away and
re-derive it as a producer fused into every product that reads it, and an
operand fused into a convolution is re-evaluated per output-tile pass: at
[32,512,3072] the exact gelu's ``erfc`` (some hundred VPU operations an
element) was evaluated 36 times a step where the model has 12 gelus, and
every dropout mask (twenty rounds of threefry) 98 times where it has 25
sites, inside products that then ran at a fifth of the MXU's rate (PERF.md
section 5, PR 28). ``jax.lax.optimization_barrier`` makes such a value one
the compiler must materialise: its consumers, forward and backward, read
it from memory.

- :func:`hold` — the barrier, in a differentiated program only.
- :func:`hold_both_ways` — the same, and on the cotangent as well.
- :func:`gelu_exact` — ``jax.nn.gelu(approximate=False)`` with its
  ``erfc`` held.
- :class:`Dropout` — ``flax.linen.Dropout`` with its keep-mask held.

Nothing here changes a rounding, a key or a mask.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@jax.custom_vjp
def hold(x):
    """Identity. Under differentiation the forward pass returns
    ``optimization_barrier(x)``, so ``x`` is computed once and what reads
    it — the rest of the forward pass and every residual the backward pass
    keeps of it — reads that one value; the cotangent passes through. A
    program that is not differentiated (``predict``, ``evaluate``, every
    serving executable) holds nothing and compiles as if ``hold`` were not
    there."""
    return x


def _hold_fwd(x):
    return lax.optimization_barrier(x), None


def _hold_bwd(_, g):
    return (g,)


hold.defvjp(_hold_fwd, _hold_bwd)


@jax.custom_vjp
def hold_both_ways(x):
    """:func:`hold` whose cotangent is held too: under differentiation
    ``x`` is materialised as it stands before anything reads it, and so is
    what the backward pass hands back for it. For a value at the door of
    a consumer that wants it in another LAYOUT than its producers work
    in: left alone, XLA's TPU compiler hoists the reshape between the two
    above the producer's conversions and elementwise chain (and sinks the
    cotangent's below the consumer's), and makes the copy on their
    float32 intermediates, several times, where one copy of the rounded
    value does."""
    return x


def _hold_both_ways_bwd(_, g):
    return (lax.optimization_barrier(g),)


hold_both_ways.defvjp(_hold_fwd, _hold_both_ways_bwd)


def gelu_exact(x):
    """``jax.nn.gelu(x, approximate=False)`` — the same constants, dtypes
    and order of operations, so value and gradient are what they are there
    bit for bit — with ``erfc(-x/sqrt(2))`` held: autodiff's residuals of
    the product are ``x`` and the held value."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.inexact):
        x = x.astype(jnp.result_type(x.dtype, float))
    sqrt_half = np.sqrt(0.5).astype(x.dtype)
    e = hold(lax.erfc(-x * sqrt_half))
    return jnp.array(0.5 * x * e, dtype=x.dtype)


class Dropout(nn.Module):
    """``flax.linen.Dropout`` with the keep-mask held: one ``make_rng``
    call, the same ``bernoulli`` draw, the same ``select``. The class keeps
    flax's name because flax folds a module's path (``.../Dropout_0``) into
    its key: under another name the same seed draws other masks."""

    rate: float
    deterministic: Optional[bool] = None

    @nn.compact
    def __call__(self, inputs, deterministic: Optional[bool] = None):
        deterministic = nn.merge_param(
            "deterministic", self.deterministic, deterministic)
        if self.rate == 0.0 or deterministic:
            return inputs
        if self.rate == 1.0:
            return jnp.zeros_like(inputs)
        keep_prob = 1.0 - self.rate
        mask = jax.random.bernoulli(
            self.make_rng("dropout"), p=keep_prob, shape=inputs.shape)
        # the site runs in training only, so the barrier needs no hold()
        mask = lax.optimization_barrier(mask)
        return lax.select(mask, inputs / keep_prob, jnp.zeros_like(inputs))
