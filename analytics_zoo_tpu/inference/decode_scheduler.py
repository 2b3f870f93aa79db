"""Step-level continuous batching for autoregressive decode (ISSUE 16).

PR 14 served generation as whole batches: a ``_GenBatch`` ran prefill
plus its entire decode loop before the engine got the executor back, so
one long generation parked every interactive encode batch behind it.
Here decode is a persistent **step-level scheduler**: a
:class:`DecodeScheduler` holds the set of live sequences and advances
them ONE wide model step at a time — between steps it admits
newly-assembled generate records (their prefill chunked across
iterations), retires finished sequences, and returns to the caller so
encode work interleaves at step granularity.

Underneath, the per-batch ``BucketedKVCache`` buffer is replaced by a
**paged KV allocator**: the decode feedback buffer lives in fixed-size
seq-axis pages drawn from one shared :class:`PagedKVAllocator` pool
sized off the ladder rungs, so rung memory is shared across concurrent
sequences — pages freed by a finishing short generation immediately
back the next admission. Page alloc/free pairing is machine-checked on
every path by the ``kv-page-leak`` zoolint lifecycle rule
(analysis/rules_lifecycle.py).

Speculative decoding rides the same step loop: a small draft model
proposes ``spec_k`` tokens which the (sharded) target model verifies in
one wide step. The acceptance rule — take draft tokens while they match
the target's greedy argmax, then the target's own token at the first
mismatch — makes greedy output **bitwise identical** to step-by-step
decode (the causal rung-padding parity of generation.py applies
unchanged), so the existing parity harness gates it directly. With no
draft model configured every sequence takes the plain one-token step.

Correctness story for interleaving: the decoder is strictly causal in
time and row-independent across the batch, so a sequence's step output
depends only on its OWN live positions — which other sequences share
the wide step, what rung the buffer padded to, and when the scheduler
paused are all invisible bitwise (tests/test_decode_scheduler.py pins
interleaved-vs-isolated equality across admission mid-flight,
preemption boundaries, and page recycling).
"""

from __future__ import annotations

from time import perf_counter
from typing import Callable, List, Optional, Sequence

import numpy as np

from analytics_zoo_tpu.common import compile_ahead, telemetry
from analytics_zoo_tpu.inference import generation

# metric handles are re-resolved from the live registry on every write
# (registering an existing family is an idempotent dict hit) — a handle
# captured at import time would go stale when telemetry.reset_for_tests
# swaps the registry singleton under a long-lived process

def _m_pages_in_use():
    return telemetry.get_registry().gauge(
        "zoo_kv_pages_in_use",
        "KV pages currently allocated to live decode sequences out of "
        "the shared pool")


def _m_pages_free():
    return telemetry.get_registry().gauge(
        "zoo_kv_pages_free",
        "KV pages currently free in the shared pool — what admission "
        "control checks before accepting a new generate sequence")


def _m_spec_proposed():
    return telemetry.get_registry().counter(
        "zoo_spec_proposed_total",
        "Draft tokens proposed by the speculative-decode draft model")


def _m_spec_accepted():
    return telemetry.get_registry().counter(
        "zoo_spec_accepted_total",
        "Draft tokens accepted by the target model's greedy verification")


def _m_spec_ratio():
    return telemetry.get_registry().gauge(
        "zoo_spec_accept_ratio",
        "Running accepted/proposed ratio of speculative decode — 1.0 "
        "means every draft token survived verification")


def _m_paged_steps():
    return telemetry.get_registry().counter(
        "zoo_paged_attn_steps_total",
        "Wide decode steps dispatched through the paged seam — the page "
        "pool consumed on device via the scalar-prefetched page table "
        "instead of a host-side gather")


def _m_paged_fallback():
    return telemetry.get_registry().counter(
        "zoo_paged_attn_fallback_total",
        "Wide decode steps that took the host gather_into fallback on a "
        "paged-capable scheduler (paged off, no verdict yet, or the "
        "autotune verdict favored gather)")


def _m_zeros_skipped():
    return telemetry.get_registry().counter(
        "zoo_kv_page_zeros_skipped_total",
        "Recycled-page memsets skipped because the paged kernel's length "
        "masking makes stale positions unreadable")


def _m_kv_requants():
    return telemetry.get_registry().counter(
        "zoo_kv_quant_requants_total",
        "int8 KV page requantizations — a later append raised a page's "
        "running amax, so its existing rows were rescaled to the grown "
        "per-page scale")


def _m_kv_pool_bytes():
    return telemetry.get_registry().gauge(
        "zoo_kv_quant_pool_bytes",
        "Resident bytes of the shared KV page pool including per-page "
        "scales — ZOO_KV_DTYPE=int8 shows up here as a ~4x drop at a "
        "fixed page count")


class PagePoolExhausted(RuntimeError):
    """The shared KV page pool cannot hold another sequence right now —
    admission should defer until a live sequence retires its pages."""


def default_pool_pages(max_batch: int, max_seq: int, spec_k: int = 4,
                       page_size: int = generation.DEFAULT_SEQ_RUNGS[0]
                       ) -> int:
    """Page count a scheduler's lazily-built allocator uses for this
    config (``admit``'s ``for_grid`` sizing: worst case per sequence is
    max_seq generated positions + the speculative draft window + one).
    ``InferenceModel.warm_decode`` sizes the paged executables' pool aval
    with it so the first live paged dispatch hits a warmed shape."""
    positions = max(1, int(max_seq) + max(0, int(spec_k)) + 1)
    per_seq = -(-positions // int(page_size))
    return max(1, int(max_batch)) * per_seq


class PagedKVAllocator:
    """Fixed-size seq-axis pages from one shared pool.

    The pool is a single ``[n_pages, page_size, dim]`` block sized off
    the ladder rungs (``for_grid``): enough pages for ``max_batch``
    concurrent worst-case sequences. Sequences own disjoint page lists,
    so a short generation finishing early returns its pages for the next
    admission regardless of what lengths are still in flight — rung
    memory is shared, never per-batch.

    Storage dtype (``kv_dtype``, default from ``ZOO_KV_DTYPE``) may be
    ``int8``: pages then hold symmetric-quantized rows with one float32
    scale per page stored alongside the pool (inference/quantize.py), a
    4x byte drop per page — at a fixed pool byte budget that multiplies
    the admissible concurrent-sequence count. ``dtype`` stays the
    LOGICAL float dtype every reader sees (gathers dequantize).

    Not thread-safe: an allocator belongs to the one scheduler (and so
    the one driving thread) that created it.
    """

    def __init__(self, n_pages: int, page_size: int, dim: int,
                 dtype=np.float32, kv_dtype=None, lazy_zero: bool = False,
                 sync_gauges: bool = True):
        from analytics_zoo_tpu.inference import quantize
        if int(n_pages) < 1 or int(page_size) < 1:
            raise ValueError("need n_pages >= 1 and page_size >= 1")
        self.page_size = int(page_size)
        self.dim = int(dim)
        self.dtype = np.dtype(dtype)
        self.kv_dtype = quantize.resolve_kv_dtype(kv_dtype)
        self.quantized = self.kv_dtype == np.dtype(np.int8)
        self._pool = np.zeros((int(n_pages), self.page_size, self.dim),
                              self.kv_dtype if self.quantized
                              else self.dtype)
        # per-page symmetric scale + the running |x|max it derives from;
        # allocated (tiny) for float pools too so pool_view keeps one
        # signature — x * 1.0 is bitwise x
        self._scales = np.ones((int(n_pages),), np.float32)
        self._amax = np.zeros((int(n_pages),), np.float32)
        self._free: List[int] = list(range(int(n_pages)))[::-1]
        self.lazy_zero = bool(lazy_zero)
        self.zeros_skipped = 0
        self._gauges_on = bool(sync_gauges)
        self._sync_gauges()

    @classmethod
    def for_grid(cls, max_batch: int, max_positions: int, dim: int,
                 page_size: int = generation.DEFAULT_SEQ_RUNGS[0],
                 dtype=np.float32, kv_dtype=None) -> "PagedKVAllocator":
        """Pool sized for ``max_batch`` concurrent sequences of up to
        ``max_positions`` each — the (batch rung × seq rung) grid's
        worst case, shared instead of per-batch."""
        per_seq = -(-max(1, int(max_positions)) // int(page_size))
        return cls(max(1, int(max_batch)) * per_seq, page_size, dim,
                   dtype, kv_dtype=kv_dtype)

    @classmethod
    def for_pool_bytes(cls, budget_bytes: int, page_size: int, dim: int,
                       dtype=np.float32, kv_dtype=None
                       ) -> "PagedKVAllocator":
        """Pool sized from a byte budget — the admission-capacity lever
        int8 KV moves: at fixed bytes, int8 pages cost ~4x less than
        float32, so the same budget admits ~4x the sequences."""
        from analytics_zoo_tpu.inference import quantize
        kv = quantize.resolve_kv_dtype(kv_dtype)
        per_page = int(page_size) * int(dim) * kv.itemsize
        if kv == np.dtype(np.int8):
            per_page += 8            # per-page scale + running amax
        n_pages = max(1, int(budget_bytes) // per_page)
        return cls(n_pages, page_size, dim, dtype, kv_dtype=kv)

    # ------------------------------------------------------------ sizing
    @property
    def n_pages(self) -> int:
        return int(self._pool.shape[0])

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_in_use(self) -> int:
        return self.n_pages - self.n_free

    def pages_for(self, positions: int) -> int:
        """Pages needed to hold ``positions`` sequence positions."""
        return -(-max(0, int(positions)) // self.page_size)

    @property
    def page_nbytes(self) -> int:
        """Bytes one page pins in the pool (row storage plus its per-page
        scale/amax entries when quantized) — what
        ``decode_kv_bytes_per_seq`` multiplies out."""
        per = int(self._pool[0].nbytes)
        if self.quantized:
            per += int(self._scales.itemsize + self._amax.itemsize)
        return per

    @property
    def pool_nbytes(self) -> int:
        return int(self._pool.nbytes + self._scales.nbytes
                   + self._amax.nbytes)

    def _sync_gauges(self):
        if not self._gauges_on:
            return
        _m_pages_in_use().set(self.n_in_use)
        _m_pages_free().set(self.n_free)
        _m_kv_pool_bytes().set(self.pool_nbytes)

    def _grow(self, extra: int):
        """Extend the pool (a single request larger than the whole pool
        must still be servable — mirrors the pre-paging behavior where
        the buffer simply grew)."""
        base = self.n_pages
        self._pool = np.concatenate(
            [self._pool,
             np.zeros((int(extra), self.page_size, self.dim),
                      self._pool.dtype)])
        self._scales = np.concatenate(
            [self._scales, np.ones((int(extra),), np.float32)])
        self._amax = np.concatenate(
            [self._amax, np.zeros((int(extra),), np.float32)])
        self._free.extend(range(base + int(extra) - 1, base - 1, -1))
        self._sync_gauges()

    # ------------------------------------------------------- alloc/free
    def alloc_pages(self, n: int) -> List[int]:
        """Take ``n`` zeroed pages from the pool. Raises
        :class:`PagePoolExhausted` when other live sequences hold too
        many pages (the caller defers admission); a single request
        bigger than the entire pool grows it instead — that is capacity
        planning, not contention."""
        n = int(n)
        if n > self.n_pages:
            self._grow(n - self.n_pages)
        if n > len(self._free):
            raise PagePoolExhausted(
                f"need {n} KV pages, {len(self._free)} free of "
                f"{self.n_pages} — waiting for a sequence to retire")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            # quant state always resets (O(1) per page): a recycled
            # page's scale must not dequantize the new owner's rows
            self._scales[p] = 1.0
            self._amax[p] = 0.0
        if self.lazy_zero:
            # the paged kernel's length masking makes stale positions
            # unreadable, so the recycle memset is pure overhead; the
            # gather fallback stays safe too (gather_into copies only
            # positions < length and the step buffer is pre-zeroed)
            self.zeros_skipped += len(pages)
            _m_zeros_skipped().inc(len(pages))
        else:
            # zero on alloc: a recycled page must not leak a previous
            # sequence's positions into the causal zero tail
            for p in pages:
                self._pool[p].fill(0)
        self._sync_gauges()
        return pages

    def free_pages(self, pages: Sequence[int]) -> None:
        """Return pages to the pool — immediately reusable by the next
        admission."""
        self._free.extend(int(p) for p in pages)
        self._sync_gauges()

    # -------------------------------------------------------- row access
    def write_row(self, page: int, off: int, vec: np.ndarray) -> None:
        """Write one position in place (the paged append seam). int8
        pools quantize under the page's symmetric scale, growing it —
        and requantizing the page's existing rows — when this row raises
        the page's running |x|max."""
        from analytics_zoo_tpu.inference import quantize
        if not self.quantized:
            self._pool[page, off, :] = vec
            return
        vec = np.asarray(vec, np.float32)
        amax = float(np.max(np.abs(vec))) if vec.size else 0.0
        if amax > self._amax[page]:
            new_scale = quantize.page_scale(amax)
            if self._amax[page] > 0.0:
                self._pool[page] = quantize.requantize_rows(
                    self._pool[page], self._scales[page], new_scale)
                _m_kv_requants().inc()
            self._scales[page] = new_scale
            self._amax[page] = amax
        self._pool[page, off, :] = quantize.quantize_rows(
            vec, self._scales[page])

    def read_row(self, page: int, off: int) -> np.ndarray:
        """One position as the logical float dtype (dequantized)."""
        from analytics_zoo_tpu.inference import quantize
        if self.quantized:
            return quantize.dequantize_rows(self._pool[page, off, :],
                                            self._scales[page])
        return self._pool[page, off, :].copy()

    def read_page(self, page: int, upto: int) -> np.ndarray:
        """The first ``upto`` rows of a page, dequantized — the SAME
        ``q * scale`` expression the paged kernel fuses, so the gather
        fallback is bitwise the kernel's view of the pool."""
        from analytics_zoo_tpu.inference import quantize
        rows = self._pool[page, :upto, :]
        if self.quantized:
            return quantize.dequantize_rows(rows, self._scales[page])
        return rows

    def pool_view(self):
        """The device-facing view ``(pool, scales)`` — the same backing
        arrays appends write in place, handed to the paged step whole
        (one upload instead of a python loop of page copies). ``scales``
        is all-ones for float pools so the paged seam keeps one
        signature; ``x * 1.0`` is bitwise ``x``."""
        return self._pool, self._scales


class PagedKVCache:
    """One sequence's decode feedback buffer, stored in allocator pages.

    Replaces the sequence's slice of the per-batch ``BucketedKVCache``:
    positions live in fixed-size pages instead of one contiguous
    ``[batch, rung, dim]`` block, so concurrent sequences of different
    lengths share pool memory. ``gather_into`` materializes the live
    positions into one row of the wide step buffer (zeros past
    :attr:`length` — the causal tail the parity claim rests on).

    Not thread-safe: a cache is owned by the one sequence holding it.
    """

    def __init__(self, alloc: PagedKVAllocator, pages: Sequence[int]):
        self._alloc = alloc
        self._pages = list(pages)
        self.length = 0

    @property
    def capacity(self) -> int:
        return len(self._pages) * self._alloc.page_size

    @property
    def n_pages(self) -> int:
        return len(self._pages)

    def _slot(self, pos: int):
        page, off = divmod(int(pos), self._alloc.page_size)
        return self._pages[page], off

    def append(self, vec: np.ndarray) -> None:
        if self.length >= self.capacity:
            # growth beyond the admission reservation: hand fresh pages
            # straight to the owned list (alloc/free stays paired — the
            # pages escape into self._pages in the same expression)
            self._pages.extend(self._alloc.alloc_pages(1))
        p, off = self._slot(self.length)
        self._alloc.write_row(p, off, vec)
        self.length += 1

    def append_block(self, mat: np.ndarray) -> None:
        """Write a chunk of positions (chunked prefill)."""
        for row in np.asarray(mat, self._alloc.dtype):
            self.append(row)

    def set(self, pos: int, vec: np.ndarray) -> None:
        p, off = self._slot(pos)
        self._alloc.write_row(p, off, vec)

    def token_id(self, pos: int) -> int:
        p, off = self._slot(pos)
        # argmax over raw storage is argmax over the dequantized row: the
        # per-page scale is one positive scalar
        return int(np.argmax(self._alloc._pool[p, off, :]))

    def row(self, pos: int) -> np.ndarray:
        p, off = self._slot(pos)
        return self._alloc.read_row(p, off)

    def truncate(self, n: int) -> None:
        """Drop positions ``>= n`` (rejected speculative drafts), zeroing
        them so later gathers see the causal zero tail again (int8 zero
        dequantizes to exact 0.0 under any scale)."""
        n = max(0, int(n))
        for pos in range(n, self.length):
            p, off = self._slot(pos)
            self._alloc._pool[p, off, :] = 0
        self.length = min(self.length, n)

    def gather_into(self, dst: np.ndarray) -> None:
        """Copy live positions into ``dst`` (``[rung, dim]``, pre-zeroed
        by the caller), dequantizing int8 pages with the same per-page
        expression the paged kernel fuses — the fallback and the kernel
        see identical bits."""
        ps = self._alloc.page_size
        pos = 0
        for page in self._pages:
            if pos >= self.length:
                break
            take = min(ps, self.length - pos)
            dst[pos:pos + take, :] = self._alloc.read_page(page, take)
            pos += take

    def page_table(self, width: int) -> np.ndarray:
        """This sequence's device-facing page-table row, padded to
        ``width`` entries with page 0 — a real page the pipelined DMA may
        prefetch, whose contents the kernel's length mask keeps out of
        the result."""
        table = np.zeros((int(width),), np.int32)
        own = self._pages[:int(width)]
        table[:len(own)] = own
        return table

    def close(self) -> None:
        """Free every page back to the pool (idempotent)."""
        pages, self._pages = self._pages, []
        self.length = 0
        self._alloc.free_pages(pages)


class DecodeSequence:
    """One live generation: its encoder row, paged cache, decode params,
    per-sequence rng stream, and the generated output buffer.
    Not thread-safe — owned by one scheduler."""

    __slots__ = ("enc", "cache", "prefill", "max_new_tokens", "mode",
                 "temperature", "rng", "gen", "generated", "tag", "lane",
                 "trace_uri", "error", "_prefill_pos", "_drafts",
                 "t_admit", "device_s", "pages_held")

    def __init__(self, enc, prefill, max_new_tokens, mode, temperature,
                 seed, cache, tag, lane, trace_uri):
        self.enc = enc
        self.prefill = prefill                  # [S, dim] teacher-forced
        self.max_new_tokens = int(max_new_tokens)
        self.mode = mode
        self.temperature = float(temperature)
        self.rng = np.random.default_rng(seed) if mode == "sample" \
            else None
        self.cache = cache
        dim = int(prefill.shape[-1])
        self.gen = np.zeros((self.max_new_tokens, dim), np.float32)
        self.generated = 0
        self.tag = tag
        self.lane = lane
        self.trace_uri = trace_uri
        self.error: Optional[BaseException] = None
        self._prefill_pos = 0
        self._drafts = 0
        self.t_admit = perf_counter()
        # cost attribution, settled by the engine when the sequence
        # finishes: device_s accumulates this sequence's share of every
        # wide step's wall time; pages_held tracks the cache's page high
        # water (captured just before close frees them)
        self.device_s = 0.0
        self.pages_held = int(cache.n_pages)

    @property
    def prefilled(self) -> bool:
        return self._prefill_pos >= self.prefill.shape[0]

    @property
    def done(self) -> bool:
        return self.generated >= self.max_new_tokens

    @property
    def result(self) -> np.ndarray:
        return self.gen

    def _feed(self, row: np.ndarray) -> np.ndarray:
        """One step's raw prediction row -> the vector fed back, via the
        same per-row feedback rule as generation.decode_loop. The rng
        stream is PER SEQUENCE, so sample output is independent of which
        other sequences shared the wide step."""
        fed = generation.feedback_rows(row[None], self.mode,
                                       self.temperature, self.rng)[0]
        self.cache.append(fed)
        self.gen[self.generated, :] = fed
        self.generated += 1
        return fed


class DecodeScheduler:
    """The persistent step-level decode loop.

    ``step_fn(enc, dec) -> [batch, t_dec, dim]`` is the full-sequence
    decoder (the model's AOT dispatch seam — e.g.
    ``InferenceModel.decode_step_fn()``). ``draft_fn`` is the same
    signature on a small draft model; with ``spec_k > 0`` greedy
    sequences decode speculatively and everything else takes the plain
    one-token step (clean fallback).

    One ``step()`` = advance chunked prefill, run ONE wide target step
    over every live sequence (padded to the batch/seq rungs the
    compile-ahead grid warmed), feed each sequence at its own position,
    and retire the finished ones. The caller owns the cadence — the
    serving engine interleaves encode batches between calls and counts
    a preemption each time it defers a step to interactive work.

    Not thread-safe: each scheduler instance is confined to its driving
    thread — the engine's serve loop owns its scheduler outright, and a
    direct ``InferenceModel.generate`` call owns a private one for the
    duration of the call. Nothing ever shares an instance across
    threads, so admit/step/drain need no internal lock.
    """

    def __init__(self, step_fn: Callable, *,
                 max_batch: int = 8,
                 max_seq: int = generation.DEFAULT_SEQ_RUNGS[1],
                 page_size: int = generation.DEFAULT_SEQ_RUNGS[0],
                 batch_ladder: Optional[compile_ahead.BucketLadder] = None,
                 allocator: Optional[PagedKVAllocator] = None,
                 draft_fn: Optional[Callable] = None, spec_k: int = 4,
                 prefill_chunk: int = 32,
                 paged_step_fn: Optional[Callable] = None,
                 paged: str = "auto"):
        if paged not in ("auto", "force", "off"):
            raise ValueError(
                f"paged must be auto|force|off, got {paged!r}")
        self._step_fn = step_fn
        # paged seam: ``(enc, pool, scales, table, lengths) ->
        # [rung, width*page_size, dim]`` — the wide TARGET step consuming
        # the page pool directly (InferenceModel.paged_decode_step_fn).
        # "auto" dispatches it per shape when the autotune verdict wins
        # (gather stays the numerics reference — never slower by
        # construction); "force"/"off" pin the path for parity tests.
        self._paged_step_fn = paged_step_fn
        self._paged = paged
        self._draft_fn = draft_fn
        self.spec_k = max(0, int(spec_k))
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.max_batch = max(1, int(max_batch))
        self.max_seq = max(2, int(max_seq))
        self.page_size = max(1, int(page_size))
        self._batch_ladder = batch_ladder or compile_ahead.BucketLadder(
            1, self.max_batch)
        self._seq_ladder = generation.seq_ladder(
            self.max_seq + self.spec_k + 1, min_rung=self.page_size)
        self._alloc = allocator
        self._prefilling: List[DecodeSequence] = []
        self._decoding: List[DecodeSequence] = []
        self._tracer = telemetry.get_tracer()
        self._spec_proposed = 0
        self._spec_accepted = 0
        self.steps_run = 0

    # ---------------------------------------------------------- admission
    @property
    def allocator(self) -> Optional[PagedKVAllocator]:
        return self._alloc

    @property
    def live(self) -> int:
        """Sequences currently admitted (prefilling + decoding)."""
        return len(self._prefilling) + len(self._decoding)

    def admit(self, enc, start, max_new_tokens: int, *,
              mode: str = "greedy", temperature: float = 1.0,
              seed: Optional[int] = None, tag=None,
              lane: str = "default",
              trace_uri: Optional[str] = None) -> DecodeSequence:
        """Admit one generation: reserve its worst-case pages up front
        (admission control — a sequence the pool cannot hold right now
        raises :class:`PagePoolExhausted` instead of stalling mid-decode)
        and queue its prefill, chunked across the next steps."""
        if mode not in generation.MODES:
            raise ValueError(
                f"mode must be one of {generation.MODES}, got {mode!r}")
        steps = int(max_new_tokens)
        if steps < 1:
            raise ValueError("max_new_tokens must be >= 1")
        enc = np.asarray(enc)
        prefill = np.asarray(start, np.float32)
        if prefill.ndim == 1:
            prefill = prefill[None, :]
        if prefill.ndim != 2:
            raise ValueError("start must be [dim] or [prefill_len, dim]")
        if self._alloc is None:
            self._alloc = PagedKVAllocator(
                default_pool_pages(self.max_batch, self.max_seq,
                                   self.spec_k, self.page_size),
                self.page_size, int(prefill.shape[-1]))
        # worst case: prefill + every generated position + a transient
        # speculative draft window past the live length
        need = self._alloc.pages_for(
            prefill.shape[0] + steps + self.spec_k)
        pages = self._alloc.alloc_pages(need)
        try:
            seq = DecodeSequence(enc, prefill, steps, mode, temperature,
                                 seed, PagedKVCache(self._alloc, pages),
                                 tag, lane, trace_uri)
        except Exception:
            self._alloc.free_pages(pages)
            raise
        self._prefilling.append(seq)
        return seq

    def abort_all(self) -> List[DecodeSequence]:
        """Drop every live sequence and free its pages (broker reconnect:
        the entries will redeliver — at-least-once, never double-ack)."""
        dropped = self._prefilling + self._decoding
        self._prefilling, self._decoding = [], []
        for seq in dropped:
            seq.cache.close()
        return dropped

    # -------------------------------------------------------------- steps
    def _advance_prefill(self):
        """Chunked prefill slotted into the decode cadence: each step
        copies at most ``prefill_chunk`` positions per sequence, so one
        long prompt cannot stall the step cadence of live decodes."""
        still = []
        for seq in self._prefilling:
            lo = seq._prefill_pos
            hi = min(lo + self.prefill_chunk, seq.prefill.shape[0])
            if hi > lo:
                seq.cache.append_block(seq.prefill[lo:hi])
                seq._prefill_pos = hi
            if seq.prefilled:
                self._decoding.append(seq)
            else:
                still.append(seq)
        self._prefilling = still

    def step(self) -> List[DecodeSequence]:
        """Advance every live sequence by one wide target step (greedy
        sequences by up to ``spec_k + 1`` tokens when a draft model is
        configured). Returns the sequences that finished this step,
        their pages already back in the pool."""
        self._advance_prefill()
        if not self._decoding:
            return []
        finished: List[DecodeSequence] = []
        # one wide call per encoder shape — heterogeneous generate kinds
        # (different params, different shapes) share the scheduler
        groups = {}
        for seq in self._decoding:
            groups.setdefault(tuple(seq.enc.shape), []).append(seq)
        for seqs in groups.values():
            for lo in range(0, len(seqs), self.max_batch):
                finished.extend(self._step_group(
                    seqs[lo:lo + self.max_batch]))
        self._decoding = [s for s in self._decoding
                          if s not in finished]
        self.steps_run += 1
        return finished

    def drain(self) -> List[DecodeSequence]:
        """Step until no sequence is live — the batch-mode cadence
        (InferenceModel.generate with a draft model rides this)."""
        out: List[DecodeSequence] = []
        while self.live:
            out.extend(self.step())
        return out

    def _materialize(self, seqs: List[DecodeSequence], seq_rung: int):
        """Stack encoder rows and gather paged caches into the wide
        ``[batch_rung, seq_rung, dim]`` step buffer the compile-ahead
        grid warmed — pad rows repeat the last sequence (pad_to_rung),
        their outputs are never read."""
        rung = self._batch_rung(len(seqs))
        enc = np.stack([s.enc for s in seqs])
        dec = np.zeros((len(seqs), seq_rung, self._alloc.dim),
                       self._alloc.dtype)
        for i, s in enumerate(seqs):
            s.cache.gather_into(dec[i])
        enc, dec = compile_ahead.pad_to_rung((enc, dec), rung,
                                             site="decode")
        return enc, dec

    def _batch_rung(self, n: int) -> int:
        rung = min(self._batch_ladder.rung_for(n), self.max_batch)
        return max(rung, n)

    def _use_paged_step(self, seqs: List[DecodeSequence],
                        seq_rung: int) -> bool:
        """Per-shape paged-vs-gather dispatch decision. ``force``/``off``
        pin the path; ``auto`` consults the autotune verdict for the
        step shape — a miss tunes on the spot in sync mode, else
        enqueues a synthetic measurement for the warmup worker and takes
        the gather reference this time (never-slower by construction)."""
        if self._paged_step_fn is None or self._paged == "off":
            return False
        if self._paged == "force":
            return True
        from analytics_zoo_tpu.ops import autotune, paged_attention
        if autotune._mode() == "off":
            return False
        rung = self._batch_rung(len(seqs))
        enc_shape = tuple(seqs[0].enc.shape)
        key = paged_attention.step_key(
            rung, seq_rung, self.page_size, self._alloc.dim,
            self._alloc.n_pages, self._alloc.kv_dtype, enc_shape)
        rec = autotune.get_tuner().lookup(key, "paged_step")
        if rec is None:
            thunk = self._paged_tune_thunk(rung, seq_rung, enc_shape, key)
            if autotune._mode() == "sync":
                rec = thunk()
            else:
                autotune.enqueue_tune(key, thunk)
                return False
        return bool(rec.get("use_kernel"))

    def _paged_tune_thunk(self, rung: int, seq_rung: int, enc_shape,
                          key: str) -> Callable[[], dict]:
        """Closure measuring one wide step via host gather vs via the
        paged seam, end to end (``Autotuner.tune_thunks`` — host thunks,
        because the gather fallback's cost is host-side python a jit
        harness cannot see). Runs on SYNTHETIC state at the live shapes:
        its own private allocator, never the serving pool."""
        step_fn, paged_fn = self._step_fn, self._paged_step_fn
        page_size, dim = self.page_size, self._alloc.dim
        n_pages, kv_dtype = self._alloc.n_pages, self._alloc.kv_dtype

        def thunk() -> dict:
            from analytics_zoo_tpu.ops import autotune
            rng = np.random.default_rng(0)
            alloc = PagedKVAllocator(n_pages, page_size, dim,
                                     kv_dtype=kv_dtype, sync_gauges=False)
            width = alloc.pages_for(seq_rung)
            fill = max(1, seq_rung - 1)
            caches = []
            for _ in range(rung):
                cache = PagedKVCache(alloc, alloc.alloc_pages(width))
                cache.append_block(
                    rng.standard_normal((fill, dim)).astype(np.float32))
                caches.append(cache)
            enc = rng.standard_normal(
                (rung,) + tuple(enc_shape)).astype(np.float32)
            table = np.stack([c.page_table(width) for c in caches])
            lengths = np.array([c.length for c in caches], np.int32)
            pool, scales = alloc.pool_view()

            def gather():
                dec = np.zeros((rung, seq_rung, dim), np.float32)
                for i, c in enumerate(caches):
                    c.gather_into(dec[i])
                return np.asarray(step_fn(enc, dec))

            def paged():
                return np.asarray(
                    paged_fn(enc, pool, scales, table, lengths))

            return autotune.get_tuner().tune_thunks(
                "paged_step", key, {"paged": paged}, gather)

        return thunk

    def tune_paged(self, batch_rung: Optional[int] = None,
                   seq_rung: Optional[int] = None,
                   enc_shape=None) -> Optional[dict]:
        """Synchronously measure gather-vs-paged for one step shape and
        persist the verdict ``paged="auto"`` dispatch consults (what
        tests call; the serve path tunes in the background instead).
        Shape arguments default to the live sequences'.
        Returns None when no paged seam or allocator exists yet."""
        if self._paged_step_fn is None or self._alloc is None:
            return None
        live = self._prefilling + self._decoding
        if batch_rung is None:
            batch_rung = self._batch_rung(max(1, len(live)))
        if seq_rung is None:
            want = max((s.cache.length + 1 for s in live), default=2)
            seq_rung = self._seq_ladder.rung_for(want)
        if enc_shape is None:
            if not live:
                raise ValueError(
                    "enc_shape is required when no sequence is live")
            enc_shape = tuple(live[0].enc.shape)
        from analytics_zoo_tpu.ops import paged_attention
        key = paged_attention.step_key(
            int(batch_rung), int(seq_rung), self.page_size,
            self._alloc.dim, self._alloc.n_pages, self._alloc.kv_dtype,
            tuple(enc_shape))
        return self._paged_tune_thunk(int(batch_rung), int(seq_rung),
                                      tuple(enc_shape), key)()

    def _paged_step(self, seqs: List[DecodeSequence],
                    seq_rung: int) -> np.ndarray:
        """The paged analog of ``_materialize`` + step: hand the step the
        pool itself plus each sequence's page table and live length — the
        gather happens on device, driven by the scalar-prefetched table.
        Pad rows repeat the last sequence's table and length (the
        pad_to_rung convention: their outputs are never read, and
        repeating keeps the dispatch identical to the gather path's)."""
        rung = self._batch_rung(len(seqs))
        width = self._alloc.pages_for(seq_rung)
        enc = np.stack([s.enc for s in seqs])
        (enc,) = compile_ahead.pad_to_rung((enc,), rung, site="decode")
        table = np.stack([s.cache.page_table(width) for s in seqs])
        lengths = np.array([s.cache.length for s in seqs], np.int32)
        if len(seqs) < rung:
            pad = rung - len(seqs)
            table = np.concatenate(
                [table, np.repeat(table[-1:], pad, axis=0)])
            lengths = np.concatenate(
                [lengths, np.repeat(lengths[-1:], pad)])
        pool, scales = self._alloc.pool_view()
        out = np.asarray(
            self._paged_step_fn(enc, pool, scales, table, lengths))
        # kernel length masking is live from here on: recycled pages stop
        # paying the memset (the gather fallback stays safe — it only
        # ever copies positions < length into a pre-zeroed buffer)
        self._alloc.lazy_zero = True
        _m_paged_steps().inc()
        return out

    def _step_group(self, seqs: List[DecodeSequence]
                    ) -> List[DecodeSequence]:
        t0 = perf_counter()
        spec = [s for s in seqs
                if self._draft_fn is not None and self.spec_k > 0
                and s.mode == "greedy"]
        if spec:
            self._propose(spec)
        seq_rung = self._seq_ladder.rung_for(
            max(s.cache.length + 1 for s in seqs))
        if self._use_paged_step(seqs, seq_rung):
            # the wide TARGET step goes paged; outputs agree bitwise with
            # the gather path because the on-device gather materializes
            # the identical (dequantized, causally zero-tailed) buffer
            out = self._paged_step(seqs, seq_rung)
        else:
            enc, dec = self._materialize(seqs, seq_rung)
            out = np.asarray(self._step_fn(enc, dec))
            if self._paged_step_fn is not None and self._paged != "off":
                _m_paged_fallback().inc()
        finished = []
        for i, s in enumerate(seqs):
            before = s.generated
            if s._drafts:
                self._verify(s, out[i])
            else:
                s._feed(out[i, s.cache.length - 1, :])
            generation.count_decode_steps(s.generated - before)
            t1 = perf_counter()
            if s.trace_uri is not None:
                for g in range(before + 1, s.generated + 1):
                    self._tracer.record(s.trace_uri, f"decode_step_{g}",
                                        t0, t1, parent="device")
            if s.done:
                s.pages_held = max(s.pages_held, s.cache.n_pages)
                s.cache.close()
                finished.append(s)
        # bill every participant an equal share of the wide step's wall
        # time — the per-request device-seconds the engine settles into
        # zoo_request_cost_device_seconds when the sequence finishes
        share = (perf_counter() - t0) / max(1, len(seqs))
        for s in seqs:
            s.device_s += share
        return finished

    # ------------------------------------------------- speculative decode
    @property
    def spec_accept_ratio(self) -> float:
        if self._spec_proposed == 0:
            return 0.0
        return self._spec_accepted / self._spec_proposed

    def _propose(self, seqs: List[DecodeSequence]):
        """Draft phase: the small model proposes up to ``spec_k`` greedy
        tokens per sequence, written into the paged cache past the live
        length (rejected ones are truncated back to zeros)."""
        want = {s: min(self.spec_k, s.max_new_tokens - s.generated)
                for s in seqs}
        for j in range(max(want.values())):
            live = [s for s in seqs if want[s] > j]
            if not live:
                break
            seq_rung = self._seq_ladder.rung_for(
                max(s.cache.length + 1 for s in live))
            enc, dec = self._materialize(live, seq_rung)
            out = np.asarray(self._draft_fn(enc, dec))
            for i, s in enumerate(live):
                row = out[i, s.cache.length - 1, :]
                fed = generation.feedback_rows(row[None], "greedy",
                                               1.0, None)[0]
                s.cache.append(fed)
                s._drafts += 1

    def _verify(self, s: DecodeSequence, out_row: np.ndarray):
        """Acceptance: walk the drafts against the target's own greedy
        argmax at each position — identical prefixes mean identical
        causal outputs, so every accepted token is bitwise the token
        step-by-step greedy would have produced; the first mismatch is
        replaced by the target's token and the rest are truncated. All
        drafts accepted earns the bonus token the wide step already
        computed."""
        k = s._drafts
        t0 = s.cache.length - k                # live length before drafts
        accepted = 0
        mismatched = False
        for j in range(k):
            if s.done:
                break
            # accepted drafts are exactly the step-by-step greedy tokens,
            # so by causality out_row[t0+j-1] is bitwise the output the
            # sequential loop would have computed at this position
            tgt = int(np.argmax(out_row[t0 + j - 1, :]))
            if tgt == s.cache.token_id(t0 + j):
                accepted += 1
                s.gen[s.generated, :] = s.cache.row(t0 + j)
                s.generated += 1
            else:
                fed = np.zeros(self._alloc.dim, np.float32)
                fed[tgt] = 1.0
                s.cache.truncate(t0 + j)       # drop this + later drafts
                s.cache.append(fed)            # target's own token instead
                s.gen[s.generated, :] = fed
                s.generated += 1
                mismatched = True
                break
        if not mismatched:
            s.cache.truncate(t0 + accepted)    # drop unconsumed drafts
            if accepted == k and not s.done:
                # every draft survived: the wide step's last position is
                # the free extra token of standard speculative decoding
                s._feed(out_row[t0 + k - 1, :])
        self._spec_proposed += k
        self._spec_accepted += accepted
        s._drafts = 0
        _m_spec_proposed().inc(k)
        _m_spec_accepted().inc(accepted)
        if self._spec_proposed:
            _m_spec_ratio().set(self._spec_accepted / self._spec_proposed)
