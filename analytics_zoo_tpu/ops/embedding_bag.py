"""Fused embedding-bag pallas kernels for the recsys path.

A recommender's embedding lookup compiles to N separate XLA gathers (one
per embedding table), each making its own pass over HBM. The kernels here
do the whole lookup in one pass:

- ``fused_embedding_lookup`` — N tables, one id column per table
  (``ids[b, t]`` indexes table ``t``), combined row-wise
  (concat / sum / mean / mul) in VMEM. The grid runs one batch element
  per step; ``pltpu.PrefetchScalarGridSpec`` prefetches the id matrix so
  each table's BlockSpec index_map points the pipeline DMA at exactly the
  gathered row — the table itself never streams through VMEM.
- ``embedding_bag`` — one table, a [batch, bag] id matrix with per-bag
  lengths, sum/mean-pooled in a VMEM fp32 accumulator (multi-hot
  categorical columns; empty bags produce exact zeros).
- ``embedding_bag_ragged`` — offsets-form bags via ``segment_sum``; pure
  jax, any backend (the fallback tier the ISSUE calls out).

Every kernel has a pure-jax reference (``*_ref``) written to accumulate
in the same order and precision as the kernel body, so fused-vs-unfused
parity is bitwise, not approximate — tests/test_embedding_bag.py holds
that line. Dispatch is verdict-driven through ops/autotune.py: the kernel
path engages only where a persisted measurement beat the reference
(never off-TPU, unless ``ZOO_PALLAS_INTERPRET`` forces interpret mode for
tests). Gradients flow through a custom VJP whose backward is a pure-jax
scatter-add — identical math to differentiating the reference gather.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_COMBINES = ("concat", "sum", "mean", "mul")


def embedding_lookup(table, ids):
    """Plain single-table gather (``table[ids]``): XLA already emits an
    optimal gather for this — kept as a named op so keras layers route
    every lookup through one module."""
    return jnp.take(table, ids, axis=0)


# ------------------------------------------------------------- references

def _fused_ref(tables, ids, combine: str):
    """Reference fused lookup, accumulation order mirroring the kernel:
    rows combine left-to-right in fp32 (except concat, which never
    accumulates), result in the tables' dtype."""
    rows = [jnp.take(t, ids[:, i], axis=0) for i, t in enumerate(tables)]
    if combine == "concat":
        return jnp.concatenate(rows, axis=-1)
    acc = rows[0].astype(jnp.float32)
    for row in rows[1:]:
        if combine == "mul":
            acc = acc * row.astype(jnp.float32)
        else:
            acc = acc + row.astype(jnp.float32)
    if combine == "mean":
        # multiply by a pre-rounded reciprocal: XLA strength-reduces the
        # constant divide this way anyway, and writing it out keeps the
        # kernel body bitwise with this reference
        acc = acc * jnp.float32(1.0 / len(rows))
    return acc.astype(tables[0].dtype)


def _bag_ref(table, ids, lengths, mean: bool):
    """Reference bag pooling, same order as the kernel: positions accumulate
    l = 0..L-1 in fp32, masked slots add exactly 0.0."""
    bag = ids.shape[1]
    acc = jnp.zeros((ids.shape[0], table.shape[1]), jnp.float32)
    for l in range(bag):
        rows = jnp.take(table, ids[:, l], axis=0).astype(jnp.float32)
        acc = acc + jnp.where((l < lengths)[:, None], rows, 0.0)
    if mean:
        acc = acc / jnp.maximum(lengths, 1).astype(jnp.float32)[:, None]
    return acc.astype(table.dtype)


def embedding_bag_ragged(table, flat_ids, offsets, mode: str = "sum"):
    """Offsets-form bags (torch ``EmbeddingBag`` convention): bag ``b``
    owns ``flat_ids[offsets[b]:offsets[b+1]]``. Pure jax ``segment_sum``
    — runs on any backend, differentiable, empty bags give zeros."""
    n_bags = offsets.shape[0] - 1
    seg = jnp.searchsorted(offsets[1:], jnp.arange(flat_ids.shape[0]),
                           side="right")
    rows = jnp.take(table, flat_ids, axis=0).astype(jnp.float32)
    pooled = jax.ops.segment_sum(rows, seg, num_segments=n_bags)
    if mode == "mean":
        counts = (offsets[1:] - offsets[:-1]).astype(jnp.float32)
        pooled = pooled / jnp.maximum(counts, 1.0)[:, None]
    return pooled.astype(table.dtype)


# ---------------------------------------------------------------- kernels

def _fused_lookup_kernel(ids_ref, *refs, dims: Tuple[int, ...],
                         combine: str):
    # refs = (row_ref per table ..., o_ref); each row_ref holds the ONE
    # [1, 1, d_t] row the index_map below DMA'd for this batch element
    o_ref = refs[-1]
    rows = [refs[t][0] for t in range(len(dims))]             # [1, d_t]
    if combine == "concat":
        off = 0
        for d_t, row in zip(dims, rows):
            o_ref[0, :, off:off + d_t] = row.astype(o_ref.dtype)
            off += d_t
        return
    acc = rows[0].astype(jnp.float32)
    for row in rows[1:]:
        if combine == "mul":
            acc = acc * row.astype(jnp.float32)
        else:
            acc = acc + row.astype(jnp.float32)
    if combine == "mean":
        acc = acc * jnp.float32(1.0 / len(dims))  # see _fused_ref
    o_ref[0] = acc.astype(o_ref.dtype)


# Rows ride as [n, 1, d] arrays with [1, 1, d] blocks: a [1, d] block of a
# 2-D array is not a legal TPU tile, a block whose last two dims are the
# whole array's is. The prefetched id matrix goes in batch-minor: SMEM pads
# the last dim to 128 words, so [batch, n] ids at NCF's batch of 8000 would
# take 4 MB of the 1 MB there is.

def _fused_pallas(tables, ids, combine: str):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from analytics_zoo_tpu.ops.flash_attention import _interp_kw

    batch = ids.shape[0]
    dims = tuple(int(t.shape[1]) for t in tables)
    d_out = sum(dims) if combine == "concat" else dims[0]

    def row_spec(t, d_t):
        # the scalar-prefetched id matrix drives the DMA: grid step b
        # pulls row ids[b, t] of table t — a gather executed by the
        # pipeline, not by kernel-body loads
        return pl.BlockSpec((1, 1, d_t), lambda b, ids_ref, _t=t: (
            ids_ref[_t, b], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch,),
        in_specs=[row_spec(t, d_t) for t, d_t in enumerate(dims)],
        out_specs=pl.BlockSpec((1, 1, d_out), lambda b, ids_ref: (b, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_fused_lookup_kernel, dims=dims, combine=combine),
        out_shape=jax.ShapeDtypeStruct((batch, 1, d_out), tables[0].dtype),
        grid_spec=grid_spec,
        **_interp_kw(),
    )(ids.T, *(t[:, None, :] for t in tables))
    return out[:, 0, :]


def _bag_kernel(ids_ref, len_ref, row_ref, o_ref, acc_ref, *, bag: int,
                mean: bool):
    import jax.experimental.pallas as pl

    b, l = pl.program_id(0), pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(l < len_ref[b])
    def _accum():
        acc_ref[...] += row_ref[0].astype(jnp.float32)

    @pl.when(l == bag - 1)
    def _flush():
        acc = acc_ref[...]
        if mean:
            acc = acc / jnp.maximum(len_ref[b], 1).astype(jnp.float32)
        o_ref[0] = acc.astype(o_ref.dtype)


def _bag_pallas(table, ids, lengths, mean: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from analytics_zoo_tpu.ops.flash_attention import _interp_kw

    batch, bag = ids.shape
    d = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, bag),
        in_specs=[pl.BlockSpec((1, 1, d), lambda b, l, ids_ref, len_ref: (
            ids_ref[l, b], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, l, ids_ref, len_ref: (
            b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_bag_kernel, bag=bag, mean=mean),
        out_shape=jax.ShapeDtypeStruct((batch, 1, d), table.dtype),
        grid_spec=grid_spec,
        **_interp_kw(),
    )(ids.T, lengths, table[:, None, :])
    return out[:, 0, :]


# ------------------------------------------------------------- custom VJPs
#
# pallas TPU kernels are not auto-differentiable; both kernel calls carry
# a custom VJP whose backward is the pure-jax scatter-add you would get
# from differentiating the reference gather — so the kernel/reference
# choice never changes training math.

def _int_zeros(a):
    # cotangent for integer primals: jax's float0 convention
    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_kernel_call(combine, tables, ids):
    return _fused_pallas(tables, ids, combine)


def _fused_fwd(combine, tables, ids):
    return _fused_pallas(tables, ids, combine), (tables, ids)


def _fused_bwd(combine, res, g):
    tables, ids = res
    n = len(tables)
    grads = []
    if combine == "concat":
        off = 0
        for i, t in enumerate(tables):
            d_t = t.shape[1]
            g_t = g[:, off:off + d_t]
            off += d_t
            grads.append(jnp.zeros_like(t).at[ids[:, i]].add(
                g_t.astype(t.dtype)))
    else:
        for i, t in enumerate(tables):
            g_t = g.astype(jnp.float32)
            if combine == "mean":
                g_t = g_t / jnp.float32(n)
            elif combine == "mul":
                for j, u in enumerate(tables):
                    if j != i:
                        g_t = g_t * jnp.take(
                            u, ids[:, j], axis=0).astype(jnp.float32)
            grads.append(jnp.zeros_like(t).at[ids[:, i]].add(
                g_t.astype(t.dtype)))
    return tuple(grads), _int_zeros(ids)


_fused_kernel_call.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bag_kernel_call(mean, table, ids, lengths):
    return _bag_pallas(table, ids, lengths, mean)


def _bag_fwd(mean, table, ids, lengths):
    return _bag_pallas(table, ids, lengths, mean), (table, ids, lengths)


def _bag_bwd(mean, res, g):
    table, ids, lengths = res
    batch, bag = ids.shape
    g_rows = g.astype(jnp.float32)[:, None, :]        # [B, 1, D]
    mask = (jnp.arange(bag)[None, :] < lengths[:, None])
    if mean:
        g_rows = g_rows / jnp.maximum(lengths, 1).astype(
            jnp.float32)[:, None, None]
    contrib = jnp.where(mask[..., None], g_rows, 0.0)  # [B, L, D]
    dt = jnp.zeros_like(table).at[ids.reshape(-1)].add(
        contrib.reshape(batch * bag, -1).astype(table.dtype))
    return dt, _int_zeros(ids), _int_zeros(lengths)


_bag_kernel_call.defvjp(_bag_fwd, _bag_bwd)


# ------------------------------------------------------------ autotuning

def _shapes_key(kind: str, shapes, extra: str, dtype) -> str:
    from analytics_zoo_tpu.ops import autotune
    dims = "+".join(f"{v}x{d}" for v, d in shapes)
    return (f"embedding_bag|{autotune._platform()}|{kind}|{extra}"
            f"|{dims}|{jnp.dtype(dtype).name}")


def tune_fused_lookup(table_shapes: Sequence[Tuple[int, int]], batch: int,
                      combine: str = "concat", dtype=jnp.float32,
                      iters: Optional[int] = None) -> dict:
    """Synchronously measure the fused kernel vs the reference for one
    (tables, batch) signature and persist the verdict."""
    from analytics_zoo_tpu.ops import autotune
    key = jax.random.PRNGKey(0)
    tables = []
    for i, (vocab, d) in enumerate(table_shapes):
        tables.append(jax.random.normal(
            jax.random.fold_in(key, i), (vocab, d), dtype))
    tables = tuple(tables)
    ids = jnp.stack([
        jax.random.randint(jax.random.fold_in(key, 100 + i), (batch,), 0,
                           vocab)
        for i, (vocab, _) in enumerate(table_shapes)], axis=1)
    return autotune.get_tuner().tune(
        "embedding_bag",
        _shapes_key("fused", table_shapes, f"{combine}.b{batch}", dtype),
        {"pallas": lambda ts, ii: _fused_kernel_call(combine, ts, ii)},
        lambda ts, ii: _fused_ref(ts, ii, combine),
        (tables, ids), iters=iters)


def tune_bag(vocab: int, dim: int, batch: int, bag: int,
             mode: str = "sum", dtype=jnp.float32,
             iters: Optional[int] = None) -> dict:
    from analytics_zoo_tpu.ops import autotune
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (vocab, dim), dtype)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (batch, bag), 0,
                             vocab)
    lengths = jax.random.randint(jax.random.fold_in(key, 2), (batch,), 0,
                                 bag + 1)
    mean = mode == "mean"
    return autotune.get_tuner().tune(
        "embedding_bag",
        _shapes_key("bag", [(vocab, dim)], f"{mode}.b{batch}l{bag}", dtype),
        {"pallas": lambda t, i, n: _bag_kernel_call(mean, t, i, n)},
        lambda t, i, n: _bag_ref(t, i, n, mean),
        (table, ids, lengths), iters=iters)


def _verdict(key: str, thunk) -> bool:
    """Shared dispatch decision: cached verdict, else sync-tune (concrete
    args + sync mode) or enqueue for the warmup worker and take the
    reference this time."""
    from analytics_zoo_tpu.ops import autotune
    if autotune._mode() == "off" or not autotune.kernels_available():
        return False
    rec = autotune.get_tuner().lookup(key, "embedding_bag")
    if rec is None and autotune._mode() == "sync":
        rec = thunk()
    if rec is None:
        autotune.enqueue_tune(key, thunk)
        return False
    return bool(rec.get("use_kernel"))


# ------------------------------------------------------------- dispatchers

def fused_embedding_lookup(tables, ids, combine: str = "concat",
                           use_kernel: Optional[bool] = None):
    """N-table fused lookup: ``ids[b, t]`` indexes ``tables[t]``; rows
    combine via ``concat`` (mixed widths ok) / ``sum`` / ``mean`` / ``mul``
    (equal widths). ``use_kernel=None`` consults the autotuner verdict —
    reference path unless a measurement proved the kernel faster."""
    assert combine in _COMBINES, combine
    tables = tuple(tables)
    ids = jnp.asarray(ids).astype(jnp.int32)
    assert ids.ndim == 2 and ids.shape[1] == len(tables), (
        f"ids {ids.shape} vs {len(tables)} tables")
    if use_kernel is None:
        shapes = tuple((int(t.shape[0]), int(t.shape[1])) for t in tables)
        batch = int(ids.shape[0])
        dtype = tables[0].dtype
        use_kernel = _verdict(
            _shapes_key("fused", shapes, f"{combine}.b{batch}", dtype),
            lambda: tune_fused_lookup(shapes, batch, combine, dtype))
    if use_kernel:
        return _fused_kernel_call(combine, tables, ids)
    return _fused_ref(tables, ids, combine)


def embedding_bag(table, ids, lengths=None, mode: str = "sum",
                  use_kernel: Optional[bool] = None):
    """Pooled multi-hot lookup: ``ids`` [batch, bag] rows of ``table``
    summed (or averaged) per bag. ``lengths`` [batch] marks the valid
    prefix of each bag (None = all valid); empty bags yield exact zeros
    (mean included — no NaN). Ids past the valid length may be anything
    in range; they are masked, not read."""
    assert mode in ("sum", "mean"), mode
    ids = jnp.asarray(ids).astype(jnp.int32)
    batch, bag = ids.shape
    if lengths is None:
        lengths = jnp.full((batch,), bag, jnp.int32)
    lengths = jnp.asarray(lengths).astype(jnp.int32)
    # clamp masked slots into range: the kernel's index_map still DMAs the
    # row before the mask applies, so every id must be a real row
    ids = jnp.clip(ids, 0, table.shape[0] - 1)
    mean = mode == "mean"
    if use_kernel is None:
        use_kernel = _verdict(
            _shapes_key("bag", [(int(table.shape[0]), int(table.shape[1]))],
                        f"{mode}.b{batch}l{bag}", table.dtype),
            lambda: tune_bag(int(table.shape[0]), int(table.shape[1]),
                             batch, bag, mode, table.dtype))
    if use_kernel:
        return _bag_kernel_call(mean, table, ids, lengths)
    return _bag_ref(table, ids, lengths, mean)
