"""A head's RMSNorm and rotary positions as one pallas kernel each way.

``GroupedQueryAttention`` normalises every head of q and of k over its
``d`` lanes, scales it, and rotates it by its row's position:

    y = rope(x · rsqrt(mean_d(x²) + eps) · scale)
    rope(u) = u · cos + rotate_half(u) · sin

Written in XLA on ``[batch, seq, heads, d]`` that chain works in (heads,
d) tiles on float32 intermediates of the whole tensor, three times a
step (forward, rematerialised forward, backward), and each crossing into
the ``[batch, seq, heads·d]`` rows the flash kernels read is a copy on
this chip. :func:`norm_rotary` takes the projection's rows as they are
and writes rows: one launch forward, one backward, float32 inside VMEM
only, rounded once at the output.

- Blocks are whole contiguous rows, ``(1, block_rows, heads·d)``; inside a
  block each head is a static ``d``-lane slice.
- ``rotate_half`` is a lane roll by ``d/2`` with the sign carried by the
  sine: ``rotate_half(u) · sin == roll(u, d/2) · [-sin | sin]``. The roll
  by half the lanes is its own inverse, so the backward's transpose of
  ``rope`` is ``dy · cos + roll(dy · [-sin | sin], d/2)``.
- The angles come in one float32 table ``[seq, d]`` per call: the
  cosines of the ``d/2`` frequencies in the first half of the lanes, the
  sines in the second (:func:`rotary_table`); positions may repeat.
- The backward writes ``dx`` and each block's partial sums of
  ``du · n`` over its rows and heads; ``dscale`` is their sum, outside.

Where it runs: ``engages`` — a TPU and a head whose width is the flash
kernels' ``rows`` layout (``flash_attention.rows_layout``), the one rule
for both. At any other width the kernels fold q and k head-major and pad
them to 128 lanes anyway, and the XLA chain feeds that copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import flash_attention
from analytics_zoo_tpu.ops.flash_attention import _interp_kw

#: bytes of one operand's block the row count is chosen for: 256 rows of
#: a ``[.., 32·128]`` bfloat16 q (of a ``[.., 4·128]`` k: ``MAX_ROWS``).
#: On a v5e the forward launches over 16,384 such rows take 0.97 ms a layer (q and
#: k, forward and recomputed) at 2 MiB and at 4 MiB, 1.41 at 1 MiB; the
#: backward 0.72 at each (PERF.md, section 6)
BLOCK_BYTES = 2 << 20
#: the most rows a block takes, whatever its width: the float32 work of a
#: head grows with the rows alone, and on a v5e 1,024 rows of 8 heads
#: (2 MiB blocks) passed the 16 MiB of VMEM a launch may hold by 0.6 MiB
#: where 512 rows of 4 heads run as fast as 2,048 (PERF.md, section 6)
MAX_ROWS = 512


def engages(head_dim: int) -> bool:
    """Whether ``GroupedQueryAttention`` runs its q/k norm and rotary
    positions through :func:`norm_rotary`."""
    return (flash_attention.on_tpu()
            and flash_attention.rows_layout(head_dim))


def rotary_table(seq: int, d: int, theta: float, positions=None):
    """``[seq, d]`` float32: the cosines of each row's ``d/2`` angles,
    then their sines — the angles ``rotary_embedding`` takes, at
    ``positions`` [seq] (default 0..seq-1)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions is None:
        positions = jnp.arange(seq, dtype=jnp.float32)
    angles = jnp.asarray(positions, jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.concatenate([jnp.cos(angles), jnp.sin(angles)], -1)


def _block_rows(seq: int, width: int, itemsize: int) -> int:
    """Rows of a block of ``BLOCK_BYTES``, at most ``MAX_ROWS``: a
    multiple of 16, or the whole sequence."""
    rows = BLOCK_BYTES // (width * itemsize) // 16 * 16
    return min(seq, MAX_ROWS, max(16, rows))


def _angles(table):
    """``(cos, signed_sin)``, each ``[rows, d]`` float32, from a block of
    the table: ``[c | c]`` and ``[-s | s]``."""
    from jax.experimental.pallas import tpu as pltpu
    d = table.shape[-1]
    swapped = pltpu.roll(table, d // 2, 1)                  # [s | c]
    first = jax.lax.broadcasted_iota(jnp.int32, table.shape, 1) < d // 2
    return (jnp.where(first, table, swapped),
            jnp.where(first, -swapped, table))


def _rms(x, eps: float):
    """``rsqrt(mean_d(x²) + eps)`` of a ``[rows, d]`` float32 head."""
    return jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm_rotary_fwd_kernel(x_ref, table_ref, scale_ref, y_ref, *,
                            heads: int, eps: float):
    from jax.experimental.pallas import tpu as pltpu
    cos, sin = _angles(table_ref[...])
    scale = scale_ref[...]                                   # [1, d]
    d = scale.shape[-1]
    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        u = x * _rms(x, eps) * scale
        y = u * cos + pltpu.roll(u, d // 2, 1) * sin
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)


def _norm_rotary_bwd_kernel(x_ref, dy_ref, table_ref, scale_ref, dx_ref,
                            dscale_ref, *, heads: int, eps: float,
                            seq: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    cos, sin = _angles(table_ref[...])
    scale = scale_ref[...]
    d = scale.shape[-1]
    partial = jnp.zeros(cos.shape, jnp.float32)
    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        r = _rms(x, eps)
        n = x * r
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        du = dy * cos + pltpu.roll(dy * sin, d // 2, 1)
        dn = du * scale
        dx = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        partial = partial + du * n
    rows = partial.shape[0]
    if seq % rows:
        # the last block runs past the sequence: its rows there hold
        # whatever the buffer held, and are no part of the sum
        row = pl.program_id(1) * rows + jax.lax.broadcasted_iota(
            jnp.int32, partial.shape, 0)
        partial = jnp.where(row < seq, partial, 0.0)
    dscale_ref[0] = jnp.sum(partial, axis=0, keepdims=True)


def _specs(x, d: int):
    import jax.experimental.pallas as pl
    b, seq, width = x.shape
    block_rows = _block_rows(seq, width, x.dtype.itemsize)
    rows = pl.BlockSpec((1, block_rows, width), lambda i, j: (i, j, 0))
    table = pl.BlockSpec((block_rows, d), lambda i, j: (j, 0))
    scale = pl.BlockSpec((1, d), lambda i, j: (0, 0))
    return (b, pl.cdiv(seq, block_rows)), rows, table, scale


def _fwd(x, scale, table, heads: int, eps: float):
    import jax.experimental.pallas as pl
    d = table.shape[-1]
    grid, rows, table_spec, scale_spec = _specs(x, d)
    return pl.pallas_call(
        functools.partial(_norm_rotary_fwd_kernel, heads=heads, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), grid=grid,
        in_specs=[rows, table_spec, scale_spec], out_specs=rows,
        **_interp_kw())(x, table, scale.astype(jnp.float32).reshape(1, d))


def _bwd(x, dy, scale, table, heads: int, eps: float):
    import jax.experimental.pallas as pl
    b, seq, _ = x.shape
    d = table.shape[-1]
    grid, rows, table_spec, scale_spec = _specs(x, d)
    blocks = grid[1]
    dx, partial = pl.pallas_call(
        functools.partial(_norm_rotary_bwd_kernel, heads=heads, eps=eps,
                          seq=seq),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b * blocks, 1, d), jnp.float32)),
        grid=grid, in_specs=[rows, rows, table_spec, scale_spec],
        out_specs=(rows, pl.BlockSpec(
            (1, 1, d), lambda i, j: (i * blocks + j, 0, 0))),
        **_interp_kw())(x, dy, table, scale.astype(jnp.float32).reshape(1, d))
    return dx, partial.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm_rotary(x, scale, table, heads, eps):
    return _fwd(x, scale, table, heads, eps)


def _norm_rotary_vjp_fwd(x, scale, table, heads, eps):
    return _fwd(x, scale, table, heads, eps), (x, scale, table)


def _norm_rotary_vjp_bwd(heads, eps, res, dy):
    x, scale, table = res
    dx, dscale = _bwd(x, dy, scale, table, heads, eps)
    return dx, dscale.astype(scale.dtype), jnp.zeros_like(table)


_norm_rotary.defvjp(_norm_rotary_vjp_fwd, _norm_rotary_vjp_bwd)


def norm_rotary(x, scale, table, heads: int, eps: float):
    """``rope(rmsnorm(x) · scale)`` of each head of ``x`` [batch, seq,
    heads·d] as ``x``'s dtype, float32 inside; ``scale`` [d] (the norm's
    parameter), ``table`` [seq, d] (:func:`rotary_table`). Differentiable
    in ``x`` and ``scale``."""
    b, seq, width = x.shape
    d = table.shape[-1]
    if width != heads * d or d % 2 or table.shape[0] != seq:
        raise ValueError(f"x {x.shape} is no [batch, {seq}, {heads}·{d}] "
                         f"for a table {table.shape}")
    return _norm_rotary(x, scale, table, heads, float(eps))
