"""Paged decode kernels: read K/V straight from the shared page pool.

The step-level decode scheduler (inference/decode_scheduler.py) keeps every
live sequence's context in fixed-size pages of one shared pool. Before this
module, each decode step paid a host-side `gather_into` — a python loop of
per-page copies assembling a contiguous ``[batch_rung, seq_rung, dim]`` step
buffer, scaling with total live context. The kernels here delete that seam:
the step consumes the pool *directly*, driven by a scalar-prefetched
per-sequence page table (the ``PrefetchScalarGridSpec`` idiom proven in
ops/embedding_bag.py — the table lands in SMEM before the grid runs, so each
grid step's ``index_map`` can pick its K/V page for the pipelined DMA).

Two primitives, both with a pure-jax numerics reference and an
interpret-mode path for CPU tests (``ZOO_PALLAS_INTERPRET=1``):

- ``paged_gather``: ``[n_pages, page_size, dim]`` pool + ``[batch, width]``
  page table + ``[batch]`` lengths → ``[batch, width*page_size, dim]``
  float32 step buffer with exact zeros at positions >= length. The length
  mask *is* the hygiene: recycled pages never need zeroing, because stale
  rows sit past every reader's length. This is the primitive the
  InferenceModel threads under its decode forward (the gather fuses into
  the jitted step, so the host loop disappears).
- ``paged_attention``: single-token decode attention ``q`` against paged
  K/V — an fp32-accumulating online-softmax inner loop over pages, with
  per-sequence length masking (a fully-masked page contributes exact-zero
  weights, so it is a no-op by construction).

int8 KV (``ZOO_KV_DTYPE=int8``): pools may be int8 with one float32
symmetric scale per page (inference/quantize.py). The dequant multiply
``q_i8.astype(f32) * scale[page]`` is fused into both kernels' inner loops
— the same expression the host fallback uses, so both paths produce
identical bits.

Dispatch follows the PR 8 discipline: ``use_kernel=None`` consults the
autotuner verdict (ops/autotune.py) — the kernel runs only where a
measurement says it beats the reference, so the auto path is never slower
than its own fallback by construction. ``step_key``/host-thunk tuning for
the scheduler-level gather-vs-paged decision lives here too (timed with
``Autotuner.tune_thunks`` because the gather fallback's cost is host-side
and invisible to a jit harness).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.flash_attention import NEG_INF, _interp_kw


def _is_int8(dtype) -> bool:
    return jnp.dtype(dtype) == jnp.dtype(jnp.int8)


# ---------------------------------------------------------------------------
# paged gather: pool + page table + lengths -> contiguous step buffer
# ---------------------------------------------------------------------------

def _gather_ref_core(pool, table, lengths, scales, quantized: bool):
    """Pure-jax gather (the numerics reference): take pages, dequantize,
    zero the causal tail. Output [batch, width*page_size, dim] float32."""
    batch, width = table.shape
    ps = pool.shape[1]
    rows = jnp.take(pool, table, axis=0).astype(jnp.float32)  # [b,w,ps,d]
    if quantized:
        rows = rows * scales[table][:, :, None, None]
    rows = rows.reshape(batch, width * ps, -1)
    pos = jax.lax.broadcasted_iota(jnp.int32, rows.shape[:2], 1)
    return jnp.where((pos < lengths[:, None])[:, :, None], rows, 0.0)


def _gather_kernel(tbl_ref, len_ref, sc_ref, pool_ref, o_ref, *,
                   page_size: int, quantized: bool):
    import jax.experimental.pallas as pl

    b, p = pl.program_id(0), pl.program_id(1)
    rows = pool_ref[0].astype(jnp.float32)                      # [ps, d]
    if quantized:
        rows = rows * sc_ref[tbl_ref[b, p]]
    pos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, rows.shape, 0)
    o_ref[0, :, :] = jnp.where(pos < len_ref[b], rows, 0.0)


def _gather_pallas(pool, table, lengths, scales, quantized: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, width = table.shape
    ps, d = int(pool.shape[1]), int(pool.shape[2])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch, width),
        in_specs=[pl.BlockSpec((1, ps, d),
                               lambda b, p, tbl, ln, sc: (tbl[b, p], 0, 0))],
        out_specs=pl.BlockSpec((1, ps, d),
                               lambda b, p, tbl, ln, sc: (b, p, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, page_size=ps, quantized=quantized),
        out_shape=jax.ShapeDtypeStruct((batch, width * ps, d), jnp.float32),
        grid_spec=grid_spec,
        **_interp_kw(),
    )(table, lengths, scales, pool)


def paged_gather_pinned(pool, table, lengths, scales=None, out_len=None,
                        *, use_kernel: bool):
    """``paged_gather`` with dispatch pinned by the caller — this path
    never touches the autotuner. It is the entry point for callers that
    run INSIDE jitted model forwards (``InferenceModel.paged_decode_step_
    fn``): tracing can happen while the model lock is held, so this seam
    must be provably free of tuner measurements (zoolint's
    blocking-under-lock interprocedural chain)."""
    pool = jnp.asarray(pool)
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    batch, width = table.shape
    ps = int(pool.shape[1])
    quantized = _is_int8(pool.dtype)
    if scales is None:
        scales = jnp.ones((pool.shape[0],), jnp.float32)
    scales = jnp.asarray(scales, jnp.float32)
    # clamp: the kernel's index_map DMAs the page before the mask applies,
    # so every table entry must name a real page (embedding_bag idiom)
    table = jnp.clip(table, 0, pool.shape[0] - 1)
    if use_kernel:
        out = _gather_pallas(pool, table, lengths, scales, quantized)
    else:
        out = _gather_ref_core(pool, table, lengths, scales, quantized)
    if out_len is not None and int(out_len) != width * ps:
        out = out[:, :int(out_len), :]
    return out


def paged_gather(pool, table, lengths, scales=None, out_len=None,
                 use_kernel: Optional[bool] = None):
    """Assemble the wide decode step buffer straight from the page pool.

    ``pool`` ``[n_pages, page_size, dim]`` (float32, or int8 with per-page
    ``scales``), ``table`` ``[batch, width]`` int32 page ids, ``lengths``
    ``[batch]`` int32 → ``[batch, out_len, dim]`` float32 with exact zeros
    at positions >= length. ``out_len`` defaults to ``width*page_size``
    and may only shrink it. ``use_kernel=None`` consults the autotuner
    verdict; the pure-jax take is the reference and the fallback."""
    pool = jnp.asarray(pool)
    if use_kernel is None:
        batch, width = np.shape(table)
        use_kernel = _verdict(
            gather_key(int(batch), int(width), int(pool.shape[1]),
                       int(pool.shape[2]), int(pool.shape[0]), pool.dtype),
            functools.partial(tune_paged_gather, int(batch), int(width),
                              int(pool.shape[1]), int(pool.shape[2]),
                              int(pool.shape[0]), pool.dtype))
    return paged_gather_pinned(pool, table, lengths, scales=scales,
                               out_len=out_len, use_kernel=bool(use_kernel))


def paged_gather_ref(pool, table, lengths, scales=None, out_len=None):
    """Reference entry point (always the pure-jax path)."""
    return paged_gather(pool, table, lengths, scales=scales,
                        out_len=out_len, use_kernel=False)


# ---------------------------------------------------------------------------
# paged decode attention: one query token vs paged K/V, online softmax
# ---------------------------------------------------------------------------

def _attn_kernel(tbl_ref, len_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref,
                 o_ref, acc_ref, m_ref, l_ref, *, page_size: int,
                 softmax_scale: float, quantized: bool):
    import jax.experimental.pallas as pl

    b, p = pl.program_id(0), pl.program_id(1)
    width = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k = k_ref[0].astype(jnp.float32)                            # [ps, d]
    v = v_ref[0].astype(jnp.float32)
    if quantized:
        page = tbl_ref[b, p]
        k = k * ks_ref[page]                 # dequant fused in-loop
        v = v * vs_ref[page]
    q = q_ref[0].astype(jnp.float32)                            # [1, d]
    # one query row per sequence: the scores are a multiply and a lane
    # reduction, not a one-row matmul. Everything stays 2-D — scores and
    # weights are [ps, 1] columns, the running max and sum [1, 1]
    s = jnp.sum(k * q, axis=1, keepdims=True) * softmax_scale   # [ps, 1]
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    live = pos < len_ref[b]
    s = jnp.where(live, s, NEG_INF)
    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    # explicit zero at masked slots: a fully-masked (recycled/padded) page
    # contributes nothing — exp(NEG_INF - NEG_INF) would be 1, not 0
    w = jnp.where(live, jnp.exp(s - m_cur), 0.0)                # [ps, 1]
    m_ref[...] = m_cur
    l_ref[...] = l_ref[...] * alpha + jnp.sum(w, axis=0, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.sum(
        w * v, axis=0, keepdims=True)                           # [1, d]

    @pl.when(p == width - 1)
    def _flush():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...]
                    / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _attn_pallas(q, k_pool, v_pool, table, lengths, k_scales, v_scales,
                 softmax_scale: float, quantized: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, width = table.shape
    ps, d = int(k_pool.shape[1]), int(k_pool.shape[2])
    page_spec = pl.BlockSpec(
        (1, ps, d), lambda b, p, tbl, ln, ks, vs: (tbl[b, p], 0, 0))
    # q and the output ride as [batch, 1, d]: a [1, d] block of a 2-D
    # array is not a legal TPU tile, a [1, 1, d] block whose last two
    # dims are the whole array's is
    row_spec = pl.BlockSpec((1, 1, d),
                            lambda b, p, tbl, ln, ks, vs: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(batch, width),
        in_specs=[row_spec, page_spec, page_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_attn_kernel, page_size=ps,
                          softmax_scale=softmax_scale, quantized=quantized),
        out_shape=jax.ShapeDtypeStruct((batch, 1, d), jnp.float32),
        grid_spec=grid_spec,
        **_interp_kw(),
    )(table, lengths, k_scales, v_scales, q[:, None, :], k_pool, v_pool)
    return out[:, 0, :]


def paged_attention_ref(q, k_pool, v_pool, table, lengths, *,
                        k_scales=None, v_scales=None, softmax_scale=None):
    """Reference einsum: gather K/V pages (dequantizing per-page scales),
    mask positions >= length, fp32 softmax, weighted sum over V."""
    q = jnp.asarray(q).astype(jnp.float32)
    d = q.shape[-1]
    sc = jnp.float32(softmax_scale if softmax_scale is not None
                     else 1.0 / math.sqrt(d))
    k = paged_gather_ref(k_pool, table, lengths, scales=k_scales)
    v = paged_gather_ref(v_pool, table, lengths, scales=v_scales)
    s = jnp.einsum("bd,bnd->bn", q, k,
                   preferred_element_type=jnp.float32) * sc
    lengths = jnp.asarray(lengths, jnp.int32)
    live = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
        < lengths[:, None]
    s = jnp.where(live, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    w = jnp.where(live, jnp.exp(s - m), 0.0)
    denom = jnp.sum(w, axis=1, keepdims=True)
    denom = jnp.where(denom == 0.0, 1.0, denom)
    out = jnp.einsum("bn,bnd->bd", w, v,
                     preferred_element_type=jnp.float32)
    return out / denom


def paged_attention(q, k_pool, v_pool, table, lengths, *, k_scales=None,
                    v_scales=None, softmax_scale=None,
                    use_kernel: Optional[bool] = None):
    """Single-token decode attention against paged K/V.

    ``q`` ``[batch, dim]``; ``k_pool``/``v_pool`` ``[n_pages, page_size,
    dim]`` (float32, or int8 with per-page ``k_scales``/``v_scales``);
    ``table`` ``[batch, width]`` page ids; ``lengths`` ``[batch]`` live
    context lengths → ``[batch, dim]`` float32. The kernel runs an
    fp32-accumulating online softmax page by page; masked positions get
    exact-zero weight, so recycled pages never need zeroing."""
    q = jnp.asarray(q)
    k_pool = jnp.asarray(k_pool)
    v_pool = jnp.asarray(v_pool)
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    batch, width = table.shape
    ps, d = int(k_pool.shape[1]), int(k_pool.shape[2])
    quantized = _is_int8(k_pool.dtype)
    n_pages = int(k_pool.shape[0])
    if k_scales is None:
        k_scales = jnp.ones((n_pages,), jnp.float32)
    if v_scales is None:
        v_scales = jnp.ones((n_pages,), jnp.float32)
    k_scales = jnp.asarray(k_scales, jnp.float32)
    v_scales = jnp.asarray(v_scales, jnp.float32)
    sc = float(softmax_scale if softmax_scale is not None
               else 1.0 / math.sqrt(d))
    table = jnp.clip(table, 0, n_pages - 1)
    if use_kernel is None:
        use_kernel = _verdict(
            attn_key(int(batch), int(width), ps, d, n_pages, k_pool.dtype),
            functools.partial(tune_paged_attention, int(batch), int(width),
                              ps, d, n_pages, k_pool.dtype))
    if use_kernel:
        return _attn_pallas(q, k_pool, v_pool, table, lengths,
                            k_scales, v_scales, sc, quantized)
    return paged_attention_ref(
        q, k_pool, v_pool, table, lengths,
        k_scales=k_scales if quantized else None,
        v_scales=v_scales if quantized else None, softmax_scale=sc)


# ---------------------------------------------------------------------------
# autotune wiring (PR 8 discipline: verdict-gated, never-slower dispatch)
# ---------------------------------------------------------------------------

def gather_key(batch: int, width: int, page_size: int, dim: int,
               n_pages: int, dtype) -> str:
    from analytics_zoo_tpu.ops import autotune
    return (f"paged_gather|{autotune._platform()}|b{batch}w{width}"
            f"p{page_size}d{dim}n{n_pages}|{jnp.dtype(dtype).name}")


def attn_key(batch: int, width: int, page_size: int, dim: int,
             n_pages: int, dtype) -> str:
    from analytics_zoo_tpu.ops import autotune
    return (f"paged_attention|{autotune._platform()}|b{batch}w{width}"
            f"p{page_size}d{dim}n{n_pages}|{jnp.dtype(dtype).name}")


def step_key(batch_rung: int, seq_rung: int, page_size: int, dim: int,
             n_pages: int, kv_dtype, enc_shape) -> str:
    """Key for the scheduler-level gather-vs-paged STEP decision (host
    thunks timed end to end — see ``Autotuner.tune_thunks``)."""
    from analytics_zoo_tpu.ops import autotune
    enc = "x".join(str(int(s)) for s in enc_shape)
    return (f"paged_step|{autotune._platform()}|b{batch_rung}s{seq_rung}"
            f"p{page_size}d{dim}n{n_pages}|enc{enc}"
            f"|{np.dtype(kv_dtype).name}")


def _synth_args(batch: int, width: int, page_size: int, dim: int,
                n_pages: int, dtype):
    key = jax.random.PRNGKey(0)
    kp, kt, kl = jax.random.split(key, 3)
    if _is_int8(dtype):
        pool = jax.random.randint(kp, (n_pages, page_size, dim),
                                  -127, 128, jnp.int32).astype(jnp.int8)
        scales = jnp.full((n_pages,), 0.01, jnp.float32)
    else:
        pool = jax.random.normal(kp, (n_pages, page_size, dim),
                                 jnp.dtype(dtype))
        scales = jnp.ones((n_pages,), jnp.float32)
    table = jax.random.randint(kt, (batch, width), 0, n_pages, jnp.int32)
    lengths = jax.random.randint(kl, (batch,), 0,
                                 width * page_size + 1, jnp.int32)
    return pool, table, lengths, scales


def tune_paged_gather(batch: int, width: int, page_size: int, dim: int,
                      n_pages: int, dtype=jnp.float32,
                      iters: Optional[int] = None) -> dict:
    """Synchronously tune the gather kernel vs the pure-jax reference on
    synthetic data at one shape; persists the verdict. Off the TPU the
    kernel cannot build and the (unpersisted) verdict is "reference"; on
    the TPU a kernel that fails to build raises."""
    from analytics_zoo_tpu.ops import autotune
    pool, table, lengths, scales = _synth_args(
        batch, width, page_size, dim, n_pages, dtype)
    quantized = _is_int8(dtype)
    return autotune.get_tuner().tune(
        "paged_gather",
        gather_key(batch, width, page_size, dim, n_pages, dtype),
        {"pallas": lambda p, t, ln, sc: _gather_pallas(
            p, t, ln, sc, quantized)},
        lambda p, t, ln, sc: _gather_ref_core(p, t, ln, sc, quantized),
        (pool, table, lengths, scales), iters=iters)


def tune_paged_attention(batch: int, width: int, page_size: int, dim: int,
                         n_pages: int, dtype=jnp.float32,
                         iters: Optional[int] = None) -> dict:
    from analytics_zoo_tpu.ops import autotune
    k_pool, table, lengths, scales = _synth_args(
        batch, width, page_size, dim, n_pages, dtype)
    v_pool = k_pool[::-1]
    q = jax.random.normal(jax.random.PRNGKey(1), (batch, dim), jnp.float32)
    quantized = _is_int8(dtype)
    sc = 1.0 / math.sqrt(dim)
    return autotune.get_tuner().tune(
        "paged_attention",
        attn_key(batch, width, page_size, dim, n_pages, dtype),
        {"pallas": lambda q, kp, vp, t, ln, ks, vs: _attn_pallas(
            q, kp, vp, t, ln, ks, vs, sc, quantized)},
        lambda q, kp, vp, t, ln, ks, vs: paged_attention_ref(
            q, kp, vp, t, ln,
            k_scales=ks if quantized else None,
            v_scales=vs if quantized else None, softmax_scale=sc),
        (q, k_pool, v_pool, table, lengths, scales, scales), iters=iters)


def gather_decision(pool, table) -> bool:
    """Verdict LOOKUP (only) for the in-jit gather dispatch
    (``InferenceModel.paged_decode_step_fn``). Deliberately no tuning —
    not even an enqueue: this runs at trace time, possibly while the
    model lock is held, so the whole path must stay measurement-free.
    The kernel engages only where a persisted verdict already says it
    wins (chip_smoke.py, tests and warmup call ``tune_paged_gather``
    explicitly);
    until then the pure-jax reference serves."""
    from analytics_zoo_tpu.ops import autotune
    if autotune._mode() == "off" or not autotune.kernels_available():
        return False
    key = gather_key(int(table.shape[0]), int(table.shape[1]),
                     int(pool.shape[1]), int(pool.shape[2]),
                     int(pool.shape[0]), pool.dtype)
    rec = autotune.get_tuner().lookup(key, "paged_gather")
    return bool(rec and rec.get("use_kernel"))


def _verdict(key: str, thunk: Callable[[], dict]) -> bool:
    """Shared dispatch decision (ops/embedding_bag.py idiom): cached
    verdict wins; a miss tunes on the spot in sync mode, else enqueues
    for the warmup worker and takes the reference this time."""
    from analytics_zoo_tpu.ops import autotune
    if autotune._mode() == "off" or not autotune.kernels_available():
        return False
    rec = autotune.get_tuner().lookup(key, "paged")
    if rec is None and autotune._mode() == "sync":
        rec = thunk()
    if rec is None:
        autotune.enqueue_tune(key, thunk)
        return False
    return bool(rec.get("use_kernel"))
