"""Gated short convolution: a causal depthwise convolution of a few taps
between two multiplicative gates.

    [B, C, u] = split3(x @ W_in);  v = B * u
    c_t = sum_j k_j * v_{t-j}      (depthwise, v zero before the start)
    y = (C * c) @ W_out

The operator of the convolution layers of hybrid decoders that interleave
it with attention. No bias anywhere. The taps are few (3), so the
convolution is a sum of shifted copies that XLA fuses with the two gates:
one pass over ``[batch, seq, 3·hidden]``, no kernel.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp


def causal_depthwise_conv(v, taps):
    """``v`` [batch, seq, channels], ``taps`` [L, channels] ->
    ``c_t = sum_j taps[j] * v[t - j]`` with ``v`` zero before position 0:
    position t sees positions t-L+1 .. t and nothing after."""
    n_taps, seq = taps.shape[0], v.shape[1]
    padded = jnp.pad(v, ((0, 0), (n_taps - 1, 0), (0, 0)))
    out = taps[0] * v
    for j in range(1, n_taps):
        out = out + taps[j] * padded[:, n_taps - 1 - j:n_taps - 1 - j + seq]
    return out


class GatedShortConv(nn.Module):
    """``x`` [batch, seq, hidden] -> [batch, seq, hidden]."""

    n_taps: int = 3
    dtype: Optional[object] = None
    kernel_init: nn.initializers.Initializer = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        bcu = nn.Dense(3 * hidden, use_bias=False, dtype=self.dtype,
                       kernel_init=self.kernel_init, name="in_proj")(x)
        gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
        taps = self.param("kernel", self.kernel_init,
                          (self.n_taps, hidden), jnp.float32)
        c = causal_depthwise_conv(gate_b * u, taps.astype(bcu.dtype))
        return nn.Dense(hidden, use_bias=False, dtype=self.dtype,
                        kernel_init=self.kernel_init,
                        name="out_proj")(gate_c * c)
