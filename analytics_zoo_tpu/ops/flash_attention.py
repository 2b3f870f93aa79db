"""Flash attention: pallas TPU kernel + blockwise-jax fallback.

New capability vs the reference (SURVEY.md §5: long-context support is
absent there — its attention is plain O(s²) matmul composition,
ref pyzoo/zoo/pipeline/api/keras/layers/self_attention.py). Two tiers:

- ``blockwise_attention`` — chunked online-softmax attention in pure jax
  (``lax.scan`` over key blocks): O(seq·block) memory, differentiable,
  runs on any backend. This is the numerics reference for the kernel.
- ``flash_attention`` — pallas TPU kernels for forward AND backward: the
  forward grid (batch·heads, the head's live tiles: ``tile_table``) runs
  online softmax in
  VMEM with fp32 accumulators and saves the per-row logsumexp; the
  backward is the FlashAttention-2 two-kernel split (dq over key blocks,
  dk/dv over query blocks) reconstructing p = exp(s − lse) — no O(s²)
  tensor ever hits HBM in either direction. MXU matmuls run in the input
  dtype with fp32 accumulation. There is no fallback inside the vjp: a
  backward that does not build is an error, not a rematerialisation.

Coverage (docs/kernels.md has the full matrix): shapes no longer need to
be tile-aligned. ``head_dim % 128 != 0`` (the 64-dim BERT class) is
zero-padded to the 128 lane — zero lanes contribute nothing to the q·k
dots and the softmax scale stays ``1/sqrt(d_orig)`` — and ragged sequence
lengths are padded to the block grid with the padded key positions masked
to −∞ inside the kernels (the same ``k_pos < kv_len`` guard
``blockwise_attention`` applies to its tail block). Padded query rows and
head lanes are sliced off the outputs and gradients.

A head's block is found by the launches' INDEX MAPS, not by copies
(``_Operands``). At a head of whole lanes (``head_dim % 128 == 0``) the
operands stay ``[batch, seq, heads·head_dim]`` as the projections write
them and a ``(1, block, head_dim)`` block at ``(batch, block index,
head)`` is the head's: no transpose before or after a launch. At any other
width such a block is no legal TPU block, and the operands are the
lane-padded head-major copies ``[batch·heads, seq, 128·k]``. In both, k
and v may come at ``kv_heads`` dividing q's heads (grouped-query
attention): the forward and ``dq`` launches name a key/value block by
``head // groups``, and the ``dk/dv`` launch keeps a key block resident
over ALL query heads of its group, so dk and dv come out at ``kv_heads``,
summed over the group in the float32 accumulators. k and v are never
repeated.

What a launch leaves out is decided by a STATIC mask (``TileMask``: the
causal one behind ``causal=True``, ``BlockDiffusionMask`` for
block-diffusion training's noisy and clean copy of a sequence): each
launch lists the tiles the mask leaves work in ahead of time
(``tile_table``) and takes no grid step for the others, and a tile the
mask's edge crosses computes only its live strips where the mask leaves
it dead ones (``strips``, ``launch_table``); the scan takes the same
object.

``ZOO_PALLAS_INTERPRET=1`` runs every kernel through the pallas
interpreter, which works on CPU — the parity tests in
tests/test_attention.py exercise the real kernel bodies without a TPU.
Block-size choice is empirical: ops/autotune.py measures candidate
(block_q, block_k) configs per shape and only dispatches the kernel when
it beats this file's blockwise reference.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

NEG_INF = -1e30

#: the names under which the backward pass's two residuals that only the
#: forward kernel can produce — its output ``[b, s, h, d]`` and the row
#: logsumexp ``[b·h, s]`` float32, padding already dropped — go through
#: ``checkpoint_name``. A ``jax.checkpoint`` whose policy keeps them
#: (``save_only_these_names(*RESIDUAL_NAMES)``) launches the forward
#: kernel once a step; any other policy, and no ``jax.checkpoint`` at
#: all, sees the identity
RESIDUAL_NAMES = ("flash_attention_out", "flash_attention_lse")

#: TPU vector lane width — the last dim tile the MXU/VPU want
LANE = 128


def ceil_to(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x``."""
    return ((x + m - 1) // m) * m


def on_tpu() -> bool:
    """The one platform test in the package: is the default backend a
    TPU (the only backend the pallas kernels compile for)."""
    return jax.devices()[0].platform == "tpu"


def pallas_interpret() -> bool:
    """``ZOO_PALLAS_INTERPRET``: run pallas kernels in interpret mode —
    slow, but executes the real kernel bodies on any backend (CPU parity
    tests). Read at trace time, so tests can flip it per-case. Refused on
    a TPU backend: there the kernels compile, and a forgotten switch would
    let the interpreter pass for them."""
    on = os.environ.get("ZOO_PALLAS_INTERPRET", "").strip().lower() in (
        "1", "true", "on", "yes")
    if on and on_tpu():
        raise RuntimeError(
            "ZOO_PALLAS_INTERPRET is set on a TPU backend; unset it — the "
            "pallas kernels must run compiled here, not interpreted")
    return on


def _interp_kw() -> dict:
    """Kwargs for ``pl.pallas_call``: pass ``interpret=True`` only when
    forced — omitting it otherwise keeps tests that monkeypatch
    ``functools.partial(pallas_call, interpret=True)`` working (an
    explicit ``interpret=False`` would override their partial)."""
    return {"interpret": True} if pallas_interpret() else {}


# ---------------------------------------------------------------- blockwise

def repeat_kv_heads(q, k, v):
    """``k`` and ``v`` [b, s, kv_heads, d] at ``q``'s heads, each
    key/value head repeated for the consecutive query heads it serves:
    what the dense path and the blockwise scan take. The kernels read a
    key/value head by its group and are never given this copy."""
    groups = q.shape[2] // k.shape[2]
    if groups == 1:
        return k, v
    return jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)


def blockwise_attention(q, k, v, causal: bool = False, block_k: int = 128,
                        return_lse: bool = False, mask=None):
    """q,k,v: [b, s, h, d] → [b, s, h, d]; O(s·block_k) memory.
    ``return_lse``: also return the per-row logsumexp as [b·h, s] fp32
    (the layout the pallas kernels use). ``mask``: a static ``TileMask``
    in place of ``causal`` (``static_mask``)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    mask = static_mask(causal, mask, sq, sk)
    block_k = min(block_k, sk)
    nk = (sk + block_k - 1) // block_k
    pad = nk * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d)
    # the causal mask is bottom-right aligned (query i sees keys <= i + sk
    # - sq), matching _reference_attention's tril(k=sk-sq) KV-cache-decode
    # semantics
    q_pos = jnp.arange(sq)

    def body(carry, kb):
        o, m, l = carry
        k_blk, v_blk, kb_idx = kb
        # fp32 scores straight off the matmul, as the kernels compute them.
        # Scores rounded to bf16 and widened again gave NaN dq/dk on a v5e
        # whenever this scan ran more than one key block and masked them
        # (fp32 inputs, the unrolled loop and the CPU were all finite; the
        # cause inside XLA's compile of the scan backward was not isolated)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        k_pos = kb_idx * block_k + jnp.arange(block_k)
        valid = k_pos < sk
        if mask is not None:
            valid = valid[None, :] & ~mask.excluded(q_pos[:, None],
                                                    k_pos[None, :])
            s = jnp.where(valid[None, None, :, :], s, NEG_INF)
        else:
            s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    k_blocks = k.reshape(b, nk, block_k, h, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, nk, block_k, h, d).transpose(1, 0, 2, 3, 4)
    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0),
                                (k_blocks, v_blocks, jnp.arange(nk)))
    out = o / jnp.maximum(l, 1e-37)[..., None]
    out = out.transpose(0, 2, 1, 3).astype(q.dtype)
    if return_lse:
        lse = (m + jnp.log(jnp.maximum(l, 1e-37))).reshape(b * h, sq)
        return out, lse
    return out


def default_use_flash(seq: int, head_dim: int, block: int = 128) -> bool:
    """Shared auto-select for the sequence-parallel compositions (ring /
    Ulysses): pallas kernels on TPU. Since the kernels pad both the head
    dim (to the 128 lane) and ragged sequence tails internally,
    ``head_dim % 128 != 0`` (e.g. 64, the BERT-class default) and
    ``seq % block != 0`` no longer disqualify a shape. The remaining
    exclusions are economic, not correctness: sequences shorter than one
    block (padding waste dominates) and head dims past 512 (VMEM scratch
    pressure at padded width)."""
    return on_tpu() and seq >= block and head_dim <= 512


def _pad_axis(a, axis: int, to: int):
    pad = to - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


# ---------------------------------------------------------------- tile table
#
# Every quantity that decides which tiles of the score matrix hold work is
# static at trace time, so a launch lists its grid steps ahead of time and
# visits nothing else. A tile (qi, ki) is one of three kinds:

#: every score of the tile is wanted: computed with no mask at all
INTERIOR = 0
#: the mask's edge or the padded key tail crosses the tile: computed under
#: the mask
DIAGONAL = 1
#: the mask allows nothing of the tile: no grid step — but for a resident
#: block with no live tile at all (causal, ``sk < sq``), which keeps one
#: step that computes nothing, so that its zeros are still written
DEAD = 2
#: the kinds by name, in the order of their codes (the labels of
#: ``zoo_flash_grid_steps``, common/profiling.py)
TILE_KINDS = ("interior", "diagonal", "dead")
#: the first code of a strip pattern: a launch codes a diagonal tile whose
#: mask leaves dead strips ``STRIPS + i``, ``i`` its pattern's index
#: (``launch_table``); such a tile counts as diagonal wherever kinds are
#: counted
STRIPS = 3
#: the side of a strip: a diagonal tile is classified in ``STRIP`` rows of
#: the resident block against ``STRIP`` columns of the streamed one, and
#: computes its live strips alone (docs/kernels.md: why 256)
STRIP = 256


class TileMask:
    """A static attention mask: which (query, key) pairs are EXCLUDED, as
    a function of their positions alone, known at trace time. It gives
    the tile table a tile's kind and the kernels the predicate of a
    DIAGONAL tile; hashable, so that it rides as a static argument."""

    #: what ``excluded`` is handed inside a kernel: the tile's row
    #: positions as a ``[block_q, 1]`` column and its key positions as a
    #: ``[1, block_k]`` row (whatever depends on one of them alone is then
    #: computed on a vector, not on the tile), or both as whole-tile iotas
    tile_iotas = False

    def excluded(self, q_pos, k_pos):
        """Boolean, broadcast over ``q_pos`` and ``k_pos`` (integer
        arrays, numpy or jax): the pairs the mask takes out."""
        raise NotImplementedError

    def representatives(self, start: int, stop: int):
        """Positions of ``[start, stop)``, ascending and ``start`` first,
        such that the mask treats every position alike with the last
        representative at or before it (all of them, unless a subclass
        knows better)."""
        return np.arange(start, stop)

    def kind(self, q0: int, q1: int, k0: int, k1: int) -> int:
        """The kind of the tile of queries ``[q0, q1)`` and keys
        ``[k0, k1)``, classified in numpy."""
        out = self.excluded(self.representatives(q0, q1)[:, None],
                            self.representatives(k0, k1)[None, :])
        return DEAD if out.all() else DIAGONAL if out.any() else INTERIOR

    def dense(self, sq: int, sk: int):
        """The ALLOWED pairs as a boolean ``[sq, sk]`` array (the dense
        path's ``mask`` argument)."""
        return ~self.excluded(jnp.arange(sq)[:, None],
                              jnp.arange(sk)[None, :])

    def check_lengths(self, sq: int, sk: int) -> None:
        """Raise if the mask is not one over ``sq`` queries and ``sk``
        keys (a mask defined for every pair of lengths: nothing)."""


@dataclasses.dataclass(frozen=True)
class CausalMask(TileMask):
    """Query ``i`` sees keys ``<= i + offset`` (``offset = sk - sq``:
    bottom-right aligned)."""

    offset: int = 0

    # the causal kernels' predicate on whole-tile iotas, as it was before
    # masks were objects: a causal launch keeps its instructions
    tile_iotas = True

    def excluded(self, q_pos, k_pos):
        return k_pos > q_pos + self.offset

    def kind(self, q0, q1, k0, k1):
        limit = q0 + self.offset               # the first query's last key
        if k0 > limit + (q1 - q0) - 1:
            return DEAD
        return DIAGONAL if k1 - 1 > limit else INTERIOR


def _where(c, a, b):
    return (np if isinstance(c, np.ndarray) else jnp).where(c, a, b)


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask(TileMask):
    """The three-region mask of block-diffusion training (BD3-LM,
    arXiv:2503.09573) over ``2 * seq_len`` rows, a NOISY copy of a
    sequence followed by its CLEAN copy, in blocks of ``block`` positions
    (``text/block_diffusion.py``):

    - a noisy row of block ``b`` sees the noisy keys of block ``b`` and
      the clean keys of blocks ``< b``;
    - a clean row of block ``b`` sees the clean keys of blocks ``<= b``
      and no noisy key.

    ``noisy=False`` is the clean half alone over ``seq_len`` rows:
    attention both ways inside a block, causal across blocks."""

    seq_len: int
    block: int
    noisy: bool = True

    def __post_init__(self):
        if self.seq_len % self.block:
            raise ValueError(f"seq_len {self.seq_len} is not a multiple of "
                             f"block {self.block}")

    @property
    def rows(self) -> int:
        """The sequence length the mask is over."""
        return self.seq_len * (2 if self.noisy else 1)

    def check_lengths(self, sq, sk):
        if not sq == sk == self.rows:
            raise ValueError(f"{self} is over {self.rows} rows; got {sq} "
                             f"queries and {sk} keys")

    def _block_of(self, pos):
        shift = self.block.bit_length() - 1
        return pos >> shift if self.block == 1 << shift \
            else pos // self.block

    def excluded(self, q_pos, k_pos):
        # a key's code: its block if clean, ``far`` + its block if noisy.
        # A clean row of block b sees the codes <= b; a noisy one the
        # codes <= b - 1 and the one code far + b: two compares on the
        # tile, everything else on the row and the column alone
        first_clean = self.seq_len if self.noisy else 0
        far = 1 << 24
        q_noisy = q_pos < first_clean
        q_block = self._block_of(_where(q_noisy, q_pos, q_pos - first_clean))
        below = _where(q_noisy, q_block - 1, q_block)
        own = _where(q_noisy, q_block + far, -1)
        code = _where(k_pos < first_clean, self._block_of(k_pos) + far,
                      self._block_of(k_pos - first_clean))
        return (code > below) & (code != own)

    def representatives(self, start, stop):
        # the mask reads a position's half and block alone, and both
        # change at multiples of ``block`` only
        inner = np.arange(-(-start // self.block) * self.block, stop,
                          self.block)
        return np.unique(np.concatenate([[start], inner])).astype(np.int64)


def static_mask(causal: bool, mask, sq: int, sk: int):
    """The ``TileMask`` of a call: ``mask`` if one is given (it must be
    over the call's lengths), the bottom-right-aligned causal one for
    ``causal``, else ``None``: every pair allowed."""
    if mask is None:
        return CausalMask(sk - sq) if causal else None
    if causal or not isinstance(mask, TileMask):
        raise ValueError("a static mask is a TileMask, given in place of "
                         "causal")
    mask.check_lengths(sq, sk)
    return mask


def _kind(mask, kv_len, q0: int, q1: int, k0: int, k1: int) -> int:
    """The kind of the queries ``[q0, q1)`` against the keys ``[k0, k1)``:
    the mask's (every pair allowed for ``None``), dead past the true key
    length ``kv_len``, diagonal where an interior region crosses it."""
    if kv_len is not None and k0 >= kv_len:
        return DEAD
    kind = INTERIOR if mask is None else mask.kind(q0, q1, k0, k1)
    if kind == INTERIOR and kv_len is not None and k1 > kv_len:
        return DIAGONAL
    return kind


@functools.lru_cache(maxsize=None)
def tile_table(nq: int, nk: int, block_q: int, block_k: int, mask, kv_len,
               key_major: bool = False, groups: int = 1):
    """The grid steps ONE head of a launch takes: an int32 ``[steps, 3]``
    array of ``(qi, ki, kind)`` rows in visiting order — for each query
    block its live key blocks ascending (forward, ``dq``), or with
    ``key_major`` for each key block its live query blocks ascending
    (``dk/dv``). The resident block's accumulator is opened on the first
    step of its run of rows and flushed on the last, so every sum takes
    the terms a rectangular grid would give it, in the same order.
    ``mask`` is the launch's ``TileMask`` or ``None``, which lists every
    tile once. ``kv_len`` is the true key length where the last key block
    is padded, else ``None``.

    ``groups`` (``key_major`` only): the query heads ONE key/value head
    serves. Beyond one the table is that key/value head's, ``[steps, 4]``
    rows of ``(qi, ki, kind, head of the group)``: a key block's run
    takes the group's heads in turn, each over the block's live query
    blocks ascending, so the block stays resident — and its ``dk``,
    ``dv`` accumulate — over every query head that read it."""
    if groups > 1 and not key_major:
        raise ValueError("a group's heads share a run of the key-major "
                         "table only")

    def kind(qi, ki):
        return _kind(mask, kv_len, qi * block_q, (qi + 1) * block_q,
                     ki * block_k, (ki + 1) * block_k)

    rows = []
    for outer in range(nk if key_major else nq):
        run = [(qi, ki, kind(qi, ki)) for qi, ki in (
            (inner, outer) if key_major else (outer, inner)
            for inner in range(nq if key_major else nk))]
        live = [t for t in run if t[2] != DEAD]
        if groups == 1:
            rows += live or run[:1]
        else:               # the kept dead step writes zeros: one does
            rows += [t + (head,) for head in range(groups) for t in live] \
                or [run[0] + (0,)]
    table = np.asarray(rows, np.int32)
    table.setflags(write=False)
    return table


@functools.lru_cache(maxsize=None)
def strips(qi: int, ki: int, block_q: int, block_k: int, mask, kv_len,
           key_major: bool = False):
    """The strip pattern of the diagonal tile ``(qi, ki)``: for each
    ``STRIP`` rows of the resident block — the query block's, or with
    ``key_major`` the key block's — ``(lo, hi, masked)``: the streamed
    block's live strips are ``[lo, hi)``, and ``masked`` says whether one
    of them crosses the mask's edge or the padded key tail. ``None``, and
    the tile is computed whole, where it has no dead strip, where a row's
    live strips are not contiguous, or where a block is no multiple of
    ``STRIP``."""
    if block_q % STRIP or block_k % STRIP:
        return None
    q0, k0 = qi * block_q, ki * block_k
    kinds = np.array([[_kind(mask, kv_len, q0 + r * STRIP,
                             q0 + (r + 1) * STRIP, k0 + c * STRIP,
                             k0 + (c + 1) * STRIP)
                       for c in range(block_k // STRIP)]
                      for r in range(block_q // STRIP)])
    if key_major:
        kinds = kinds.T
    if not (kinds == DEAD).any():
        return None
    pattern = []
    for row in kinds:
        live = np.flatnonzero(row != DEAD)
        lo, hi = (int(live[0]), int(live[-1]) + 1) if len(live) else (0, 0)
        if hi - lo != len(live):
            return None
        pattern.append((lo, hi, bool((row[lo:hi] == DIAGONAL).any())))
    return tuple(pattern)


def _segments(pattern) -> tuple:
    """A strip pattern as the products its body computes, ``(r0, r1, c0,
    c1, masked)`` in rows of the resident and the streamed block: one a
    strip, none for a strip that sees nothing."""
    return tuple((r * STRIP, (r + 1) * STRIP, lo * STRIP, hi * STRIP, masked)
                 for r, (lo, hi, masked) in enumerate(pattern) if hi > lo)


@functools.lru_cache(maxsize=None)
def launch_table(nq: int, nk: int, block_q: int, block_k: int, mask,
                 kv_len, key_major: bool = False, groups: int = 1):
    """What ONE head of a launch runs: ``tile_table``'s rows (the same
    arguments) in its order, with each diagonal tile whose mask leaves it
    dead strips coded ``STRIPS + i`` for its pattern (``strips``), and
    ``((code, segments), ...)`` for each code the table holds — the
    products of a step of that kind, ``(r0, r1, c0, c1, masked)`` in rows
    of the resident and the streamed block: an interior tile whole and
    bare, a diagonal one whole and masked, a pattern's tile its live
    strips, a dead one nothing."""
    table = tile_table(nq, nk, block_q, block_k, mask, kv_len, key_major,
                       groups).copy()
    resident, streamed = (block_k, block_q) if key_major \
        else (block_q, block_k)
    bodies = {INTERIOR: ((0, resident, 0, streamed, False),),
              DIAGONAL: ((0, resident, 0, streamed, True),), DEAD: ()}
    patterns = []
    for row in table:
        if row[2] != DIAGONAL:
            continue
        pattern = strips(int(row[0]), int(row[1]), block_q, block_k, mask,
                         kv_len, key_major)
        if pattern is None:
            continue
        if pattern not in patterns:
            patterns.append(pattern)
            bodies[STRIPS + len(patterns) - 1] = _segments(pattern)
        row[2] = STRIPS + patterns.index(pattern)
    table.setflags(write=False)
    return table, tuple((code, bodies[code])
                        for code in sorted(set(table[:, 2].tolist())))


@functools.lru_cache(maxsize=None)
def tile_pairs(nq: int, nk: int, block_q: int, block_k: int, mask, kv_len,
               key_major: bool = False, groups: int = 1):
    """``(computed, allowed)``: the score pairs the live steps of ONE
    head's launch compute (``launch_table``, the same arguments: a whole
    tile, or a diagonal tile's live strips), and those of them that the
    mask and the true key length ``kv_len`` let through — an interior tile
    whole, a diagonal one counted here in numpy. Padded query rows count
    as the mask treats their positions."""
    table, bodies = launch_table(nq, nk, block_q, block_k, mask, kv_len,
                                 key_major, groups)
    area = {code: sum((r1 - r0) * (c1 - c0) for r0, r1, c0, c1, _ in segs)
            for code, segs in bodies}
    live = table[table[:, 2] != DEAD]
    allowed = sum(
        block_q * block_k if kind == INTERIOR else _allowed_in_tile(
            int(qi), int(ki), block_q, block_k, mask, kv_len)
        for qi, ki, kind in live[:, :3])
    return sum(area[code] for code in live[:, 2].tolist()), allowed


@functools.lru_cache(maxsize=None)
def _allowed_in_tile(qi: int, ki: int, block_q: int, block_k: int, mask,
                     kv_len) -> int:
    """The pairs of tile ``(qi, ki)`` that neither the mask nor the padded
    key tail excludes: the mask read at its representatives, each weighed
    by the positions it stands for."""
    q0, k0 = qi * block_q, ki * block_k
    k1 = k0 + block_k if kv_len is None else min(k0 + block_k, kv_len)
    if k1 <= k0:
        return 0
    if mask is None:
        return block_q * (k1 - k0)
    qs = mask.representatives(q0, q0 + block_q)
    ks = mask.representatives(k0, k1)
    seen = ~mask.excluded(qs[:, None], ks[None, :])
    return int(np.diff(qs, append=q0 + block_q)
               @ seen.astype(np.int64) @ np.diff(ks, append=k1))


def _step(qi_ref, ki_ref, kind_ref, key_major: bool = False):
    """This grid step's row of the table, and whether it is the first and
    the last of its resident block's run (``qi``'s, or with ``key_major``
    ``ki``'s): ``(qi, ki, kind, first, last)``, scalars."""
    import jax.experimental.pallas as pl

    step, last_step = pl.program_id(1), pl.num_programs(1) - 1
    row_ref = ki_ref if key_major else qi_ref
    row = row_ref[step]
    first = (step == 0) | (row_ref[jnp.maximum(step - 1, 0)] != row)
    last = (step == last_step) | (
        row_ref[jnp.minimum(step + 1, last_step)] != row)
    return qi_ref[step], ki_ref[step], kind_ref[step], first, last


def _by_kind(kind, bodies, compute) -> None:
    """Run ``compute(segments)`` with the step's kind's segments
    (``launch_table``): a whole tile bare, a whole tile masked, a
    pattern's live strips, nothing on a dead step. ``bodies`` are the
    launch's ``(code, segments)``; one that holds a single kind computes
    unconditionally."""
    import jax.experimental.pallas as pl

    if len(bodies) == 1:
        if bodies[0][1]:
            compute(bodies[0][1])
        return
    for code, segments in bodies:
        if segments:
            pl.when(kind == code)(functools.partial(compute, segments))


def _masked_scores(s, q0, k0, *, mask, kv_len):
    """Scores of the queries from ``q0`` against the keys from ``k0``
    with what the mask and the padded key tail exclude set to ``NEG_INF``
    — the kernel-side mirror of ``blockwise_attention``'s
    ``mask.excluded`` and ``k_pos < sk``."""
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)
    k_pos = k0 + iota(s.shape, 1)
    masked = None
    if mask is not None and mask.tile_iotas:
        q_pos = q0 + iota(s.shape, 0)
        masked = mask.excluded(q_pos, k_pos)
    elif mask is not None:
        masked = mask.excluded(q0 + iota((s.shape[0], 1), 0),
                               k0 + iota((1, s.shape[1]), 1))
    if kv_len is not None:
        over = k_pos >= kv_len
        masked = over if masked is None else (masked | over)
    return s if masked is None else jnp.where(masked, NEG_INF, s)


def _tile_call(kernel, tiles, heads: int, operands: "_Operands", *,
               key_major: bool = False, out_shape, in_specs, out_specs,
               scratch_shapes):
    """``pl.pallas_call`` of ``kernel`` over ``(heads, the steps of one
    head's launch_table(*tiles, key_major, groups))``: the table's columns
    go ahead of the operands by scalar prefetch, to the kernel and to
    every index map, which take ``(head, step, qi_ref, ki_ref, kind_ref)``
    and, where a key/value head's table lists its group's heads, a fourth
    ``head_ref``; the kernel gets each kind's segments as ``bodies``.
    One-dimensional columns: SMEM pads an array's last dim to 128 words,
    so ``[steps, 3]`` as it stands would take 512 bytes a step. The steps
    the launch takes, by kind (``tile_table``'s: a pattern's tile is
    diagonal), ride in the custom call as its kernel metadata beside the
    score pairs they compute and allow (``pairs``, one head's
    ``tile_pairs``) and how it finds a head's blocks — ``layout`` and
    ``kv``, ``_Operands`` — (``profiling.count_flash_grid_steps``,
    ``count_flash_score_pairs`` and ``count_flash_layouts`` read them from
    the compiled program)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    groups = operands.groups if key_major else 1
    table, bodies = launch_table(*tiles, key_major, groups)
    counts = np.bincount(tile_table(*tiles, key_major, groups)[:, 2],
                         minlength=len(TILE_KINDS)) * heads
    computed, allowed = tile_pairs(*tiles, key_major, groups)
    call = pl.pallas_call(
        functools.partial(kernel, bodies=bodies),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=table.shape[1], grid=(heads, len(table)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        metadata={**{name: str(n) for name, n in zip(TILE_KINDS, counts)},
                  "pairs": str(computed * heads),
                  "allowed": str(allowed * heads),
                  "layout": "rows" if operands.rows else "heads",
                  "kv": "grouped" if operands.groups > 1 else "own"},
        **_interp_kw())
    columns = [jnp.asarray(table[:, c]) for c in range(table.shape[1])]
    return lambda *arrays: call(*columns, *arrays)


# ---------------------------------------------------------------- pallas fwd

def _flash_fwd_kernel(qi_ref, ki_ref, kind_ref, q_ref, k_ref, v_ref, o_ref,
                      *rest, bodies, sm_scale: float, block_q, block_k,
                      **tile):
    import jax.experimental.pallas as pl

    # rest = (lse_ref?, o_scr, m_scr, l_scr): the lse output only exists
    # when the caller asked for it (training) — inference keeps the old
    # single-output forward and pays nothing for it
    lse_ref = rest[0] if len(rest) == 4 else None
    o_scr, m_scr, l_scr = rest[-3:]

    qi, ki, kind, first, last = _step(qi_ref, ki_ref, kind_ref)

    @pl.when(first)
    def _init():
        o_scr[...] = jnp.zeros_like(o_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def _compute(segments):
        # MXU matmuls stay in the input dtype (bf16 doubles throughput on
        # v5e); softmax state and the output accumulator are fp32 — the
        # standard flash mixed-precision split. preferred_element_type
        # gives fp32 accumulation inside the MXU either way. sm_scale is
        # 1/sqrt(d_orig) from the caller: q may be zero-padded past the
        # model's head_dim, so q.shape[-1] is the wrong denominator here.
        # A segment is the query rows [r0, r1) against the streamed keys
        # [c0, c1): the whole tile, or one run of a pattern's strips.
        # Every segment's scores first, then each one's softmax: the
        # compiler keeps the order it is given, and a strip's softmax
        # then runs beside the next strips' products
        scores = []
        for r0, r1, c0, c1, masked in segments:
            q = q_ref[0, r0:r1]                  # [rows, d]
            k_blk = k_ref[0, c0:c1]              # [cols, d] (streamed)
            s = jax.lax.dot_general(             # [rows, cols] fp32
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                s = _masked_scores(s, qi * block_q + r0, ki * block_k + c0,
                                   **tile)
            scores.append(s)
        for (r0, r1, c0, c1, _), s in zip(segments, scores):
            v_blk = v_ref[0, c0:c1]
            # softmax state stays 2-D ([rows, 1] columns) end to end:
            # Mosaic works in sublane × lane tiles, and a column
            # broadcasts along the lanes as it is
            m = m_scr[r0:r1]
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            pv = jax.lax.dot_general(            # p in v's dtype → MXU rate
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_scr[r0:r1] = o_scr[r0:r1] * corr + pv
            l_scr[r0:r1] = l_scr[r0:r1] * corr + p.sum(-1, keepdims=True)
            m_scr[r0:r1] = m_new

    _by_kind(kind, bodies, _compute)

    @pl.when(last)
    def _flush():
        l_fin = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (o_scr[...] / l_fin).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp per query row (scaled-score space) — the backward
            # kernels reconstruct p = exp(s - lse) from it. Stored
            # replicated across the 128 lanes: a [block_q, 1] block is not
            # a legal TPU output tile, the caller keeps lane 0
            lse_ref[0] = jnp.broadcast_to(m_scr[...] + jnp.log(l_fin),
                                          lse_ref.shape[1:])


def _pad_blocks(q, k, v, block_q: int, block_k: int):
    """Clamp blocks to the (tile-rounded) sequence lengths, then pad seq
    dims to the block grid and the head dim to the lane width (nothing to
    pad at a head of 128 lanes or a multiple). Returns the padded
    ``[b, seq, heads, d]`` tensors, effective blocks, and the padded dims.
    ``block_q`` is a lane multiple unless one block covers the whole query
    length: the backward reads the per-row statistics as lane-dense
    ``[1, block_q]`` rows, and a TPU block's last dim is a multiple of 128
    or the full dim."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = min(ceil_to(block_q, LANE), ceil_to(sq, 16))
    block_k = min(block_k, ceil_to(sk, 16))
    sq_p, sk_p = ceil_to(sq, block_q), ceil_to(sk, block_k)
    d_p = ceil_to(d, LANE)
    q = _pad_axis(_pad_axis(q, 1, sq_p), 3, d_p)
    k = _pad_axis(_pad_axis(k, 1, sk_p), 3, d_p)
    v = _pad_axis(_pad_axis(v, 1, sk_p), 3, d_p)
    return q, k, v, block_q, block_k, sq_p, sk_p, d_p


def _div(i, n: int):
    """``i // n``, and ``i`` itself for one."""
    return i if n == 1 else i // n


@dataclasses.dataclass(frozen=True)
class _Operands:
    """How the launches of one call find a head's blocks: the layout of
    their operands and the index maps over them. One rule picks the
    layout, from the head's width alone (``rows_layout``):

    - ``rows`` (``head_dim % 128 == 0``): q, dO, out, dq stay ``[batch,
      seq, heads·d]`` as the projections write them, k, v, dk, dv
      ``[batch, seq, kv_heads·d]``; a ``(1, block, d)`` block at ``(batch,
      block index, head)`` IS the head's block, no transpose on either
      side of a launch.
    - ``heads`` (any other width): a ``d``-wide block of such an array is
      no legal TPU block, so the operands are padded to the 128 lanes and
      folded head-major, ``[batch·heads, seq, d_p]``.

    Either way a key/value head is read by the ``groups`` consecutive
    query heads it serves — its blocks are named ``head // groups`` — and
    is never repeated. The grid's first index is ``batch·heads + head``;
    ``by_group`` is the ``dk/dv`` launch, whose first index counts
    key/value heads and whose table names the head of the group
    (``tile_table``)."""

    heads: int
    groups: int
    rows: bool
    by_group: bool = False

    @property
    def kv_heads(self) -> int:
        return self.heads // self.groups

    def fold(self, a):
        """A padded ``[b, seq, h, d]`` operand as the kernels read it."""
        b, s, h, d = a.shape
        if self.rows:
            return a.reshape(b, s, h * d)
        return a.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    def unfold(self, a, b: int, kv: bool = False):
        """A result of the kernels back as ``[b, seq, h, d]`` (``kv``: at
        the key/value heads)."""
        h = self.kv_heads if kv else self.heads
        if self.rows:
            return a.reshape(b, a.shape[1], h, -1)
        return a.reshape(b, h, *a.shape[1:]).transpose(0, 2, 1, 3)

    def _heads_of(self, i, step, refs):
        """This step's query head and key/value head, both counted over
        the batch."""
        if self.by_group and self.groups > 1:
            return i * self.groups + refs[3][step], i
        return i, _div(i, self.groups)

    def q_block(self, i, step, *refs):
        """Index map of a query-side operand's block."""
        n, _ = self._heads_of(i, step, refs)
        if self.rows:
            return n // self.heads, refs[0][step], n % self.heads
        return n, refs[0][step], 0

    def k_block(self, i, step, *refs):
        """Index map of a key-side operand's block."""
        _, m = self._heads_of(i, step, refs)
        if self.rows:
            return m // self.kv_heads, refs[1][step], m % self.kv_heads
        return m, refs[1][step], 0

    def q_lanes(self, i, step, *refs):
        """Index map of the query block's ``[b·h, seq, LANE]`` rows (the
        forward's logsumexp, head-major in either layout)."""
        return self._heads_of(i, step, refs)[0], refs[0][step], 0

    def q_stats(self, i, step, *refs):
        """Index map of the query block's ``[b·h, 1, seq]`` statistics."""
        return self._heads_of(i, step, refs)[0], 0, refs[0][step]


def rows_layout(head_dim: int) -> bool:
    """The one rule for whether a head's data stays in ``[batch, seq,
    heads·head_dim]`` rows: a head of whole lanes. The kernels' operands
    take the ``rows`` layout by it (``_Operands``), and the q/k norm and
    rotary positions run as one kernel on such rows by it
    (``ops/norm_rotary.py``)."""
    return head_dim % LANE == 0


def _operands(q, k) -> _Operands:
    """The layout and grouping of a call over ``q`` [b, sq, h, d] and
    ``k`` [b, sk, kv_heads, d]."""
    h, kv_heads = q.shape[2], k.shape[2]
    if h % kv_heads:
        raise ValueError(f"{h} query heads are no multiple of {kv_heads} "
                         "key/value heads")
    return _Operands(h, h // kv_heads, rows_layout(q.shape[3]))


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               return_lse: bool = False, mask=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    # the mask is defined by the ORIGINAL lengths (the causal one is
    # bottom-right aligned, see blockwise_attention); padding must not
    # shift it
    mask = static_mask(causal, mask, sq, sk)
    sm_scale = 1.0 / math.sqrt(d)
    ops = _operands(q, k)
    q, k, v, block_q, block_k, sq_p, sk_p, d_p = _pad_blocks(
        q, k, v, block_q, block_k)
    # the leading grid dim is (batch, head) folded; the second is the
    # head's live tiles in the table's order: k/v stream through VMEM one
    # block per step (pallas double-buffers the HBM loads, and a block the
    # next step names again is not loaded again), accumulators persist in
    # VMEM scratch across a query block's steps. A head's block is found
    # by the index maps (``_Operands``): in the projections' own layout at
    # a head of whole lanes, in head-major copies otherwise; a key/value
    # head by its group in both.
    qt, kt, vt = ops.fold(q), ops.fold(k), ops.fold(v)
    kv_len = sk if sk_p != sk else None
    tiles = (sq_p // block_q, sk_p // block_k, block_q, block_k, mask,
             kv_len)
    q_spec = pl.BlockSpec((1, block_q, d_p), ops.q_block)
    k_spec = pl.BlockSpec((1, block_k, d_p), ops.k_block)
    out_shape = [jax.ShapeDtypeStruct(qt.shape, q.dtype)]
    out_specs = [q_spec]
    if return_lse:
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, sq_p, LANE), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_q, LANE), ops.q_lanes))
    res = _tile_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k,
                          block_q=block_q, mask=mask, sm_scale=sm_scale,
                          kv_len=kv_len),
        tiles, b * h, ops,
        out_shape=tuple(out_shape),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((block_q, d_p), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )(qt, kt, vt)
    out, lse = res if return_lse else (res[0], None)
    out = ops.unfold(out, b)[:, :sq, :, :d]     # drop padded rows/lanes
    if return_lse:
        return out, lse[:, :sq, 0]
    return out


# ---------------------------------------------------------------- pallas bwd
#
# Standard FlashAttention-2 backward split into two kernels (no atomics on
# TPU): dq accumulates over key blocks with the query block resident; dk/dv
# accumulate over query blocks with the key block resident. Both
# reconstruct p = exp(s·scale − lse) from the forward's saved logsumexp and
# use Δ = rowsum(dO ⊙ O) for the softmax Jacobian. MXU matmuls run in the
# input dtype with fp32 accumulation; accumulators live in VMEM scratch.

def _bwd_block(q, k_blk, v_blk, do, lse, delta, q0, k0, masked: bool, *,
               sm_scale, **tile):
    """Shared per-segment math: returns (p, ds) as fp32 [rows, cols] for
    the queries from position ``q0`` against the keys from ``k0``.
    ``lse`` and ``delta`` are [rows] rows; ``delta`` already has the
    cotangent of the row logsumexp subtracted (see ``_flash_bwd``).
    Padded query rows arrive with lse = +1e30 so p (and everything
    downstream) is exactly zero for them."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if masked:
        s = _masked_scores(s, q0, k0, **tile)
    p = jnp.exp(s - lse[:, None])                     # [bq, bk] fp32
    dp = jax.lax.dot_general(                         # dO · Vᵀ
        do, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * sm_scale
    return p, ds


def _flash_bwd_dq_kernel(qi_ref, ki_ref, kind_ref, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
                         bodies, block_q, block_k, **tile):
    import jax.experimental.pallas as pl

    qi, ki, kind, first, last = _step(qi_ref, ki_ref, kind_ref)

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(segments):
        # a segment: the query rows [r0, r1) against the keys [c0, c1);
        # every segment's dS first, then the products that accumulate
        # them (the forward's order, for the same reason)
        grads = []
        for r0, r1, c0, c1, masked in segments:
            q, k_blk, v_blk = q_ref[0, r0:r1], k_ref[0, c0:c1], \
                v_ref[0, c0:c1]
            _, ds = _bwd_block(q, k_blk, v_blk, do_ref[0, r0:r1],
                               lse_ref[0, 0, r0:r1], delta_ref[0, 0, r0:r1],
                               qi * block_q + r0, ki * block_k + c0, masked,
                               **tile)
            grads.append((ds.astype(q.dtype), k_blk))
        for (r0, r1, *_), (ds, k_blk) in zip(segments, grads):
            dq_scr[r0:r1] += jax.lax.dot_general(     # dS · K
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _by_kind(kind, bodies, _compute)

    @pl.when(last)
    def _flush():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(qi_ref, ki_ref, kind_ref, *refs, bodies, groups,
                          block_q, block_k, **tile):
    import jax.experimental.pallas as pl

    # a key/value head's table names the head of its group in a fourth
    # column, for the index maps alone: the key block's run, over which
    # dk and dv accumulate, is all of the group's heads
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
     dk_scr, dv_scr) = refs[1:] if groups > 1 else refs
    qi, ki, kind, first, last = _step(qi_ref, ki_ref, kind_ref,
                                      key_major=True)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute(segments):
        # the key-major table's segment: the resident key rows [r0, r1)
        # against the streamed query rows [c0, c1); every segment's P and
        # dS first, then the products that accumulate them
        grads = []
        for r0, r1, c0, c1, masked in segments:
            q, do = q_ref[0, c0:c1], do_ref[0, c0:c1]
            k_blk, v_blk = k_ref[0, r0:r1], v_ref[0, r0:r1]
            p, ds = _bwd_block(q, k_blk, v_blk, do, lse_ref[0, 0, c0:c1],
                               delta_ref[0, 0, c0:c1], qi * block_q + c0,
                               ki * block_k + r0, masked, **tile)
            grads.append((p.astype(do.dtype), do, ds.astype(q.dtype), q))
        for (r0, r1, *_), (p, do, ds, q) in zip(segments, grads):
            dv_scr[r0:r1] += jax.lax.dot_general(     # Pᵀ · dO
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_scr[r0:r1] += jax.lax.dot_general(     # dSᵀ · Q
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _by_kind(kind, bodies, _compute)

    @pl.when(last)
    def _flush():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _row_sums(x, heads: int):
    """Each head's sum over its ``d`` lanes of ``x`` [b, seq, heads·d]
    float32, as ``[b·heads, seq]``: a product with the heads' indicator
    columns. A ``reshape`` to ``[b, seq, heads, d]`` and a sum over ``d``
    say the same, and XLA's TPU compiler then copies ``x`` from (seq,
    lanes) tiles into (heads, d) tiles to take it; the product reads
    ``x`` where it lies. The indicator is exact in any precision and ``x``
    goes at the highest, so the sums are float32 sums."""
    b, s, width = x.shape
    d = width // heads
    of_head = (jnp.arange(width)[:, None] // d
               == jnp.arange(heads)[None, :]).astype(jnp.float32)
    return jnp.einsum("bsk,kh->bhs", x, of_head,
                      precision=jax.lax.Precision.HIGHEST).reshape(
                          b * heads, s)


def _flash_bwd(q, k, v, o, lse, g, causal: bool, block_q: int,
               block_k: int, g_lse=None, mask=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    mask = static_mask(causal, mask, sq, sk)
    sm_scale = 1.0 / math.sqrt(d)
    ops = _operands(q, k)
    q, k, v, block_q, block_k, sq_p, sk_p, d_p = _pad_blocks(
        q, k, v, block_q, block_k)
    o = _pad_axis(_pad_axis(o, 1, sq_p), 3, d_p)
    g = _pad_axis(_pad_axis(g, 1, sq_p), 3, d_p)
    qt, kt, vt, dot = ops.fold(q), ops.fold(k), ops.fold(v), ops.fold(g)
    # Δ = rowsum(dO ⊙ O): cheap elementwise, stays outside the kernels,
    # taken in the operands' layout; its [b·h, seq] rows alone are
    # head-major in both. Padded query rows have dO = 0, so Δ = 0 there.
    if ops.rows:
        delta = _row_sums(dot.astype(jnp.float32)
                          * ops.fold(o).astype(jnp.float32), h)
    else:
        delta = jnp.sum(dot.astype(jnp.float32)
                        * ops.fold(o).astype(jnp.float32), axis=-1)
    if g_lse is not None:
        # the cotangent of the row logsumexp folds into the same
        # softmax-Jacobian term as Δ, since ∂lse_i/∂s_ij = p_ij
        delta = delta - _pad_axis(g_lse.astype(jnp.float32), 1, sq_p)
    # padded query rows get lse = +1e30 → p = exp(s − 1e30) ≡ 0 in the
    # tiles, so they contribute exactly nothing to dk/dv (and their dq
    # rows, whatever they hold, are sliced off below)
    lse = jnp.pad(lse.astype(jnp.float32), ((0, 0), (0, sq_p - sq)),
                  constant_values=-NEG_INF)
    # per-row statistics ride as [b·h, 1, sq_p]: each kernel step takes a
    # lane-dense [1, block_q] row (a [1, block_q] block of a 2-D array is
    # not a legal TPU tile)
    lse, delta = lse[:, None, :], delta[:, None, :]
    kv_len = sk if sk_p != sk else None
    tile = dict(block_q=block_q, block_k=block_k, mask=mask,
                sm_scale=sm_scale, kv_len=kv_len)
    tiles = (sq_p // block_q, sk_p // block_k, block_q, block_k, mask,
             kv_len)

    def in_specs(maps):
        q_spec = pl.BlockSpec((1, block_q, d_p), maps.q_block)
        k_spec = pl.BlockSpec((1, block_k, d_p), maps.k_block)
        r_spec = pl.BlockSpec((1, 1, block_q), maps.q_stats)
        return [q_spec, k_spec, k_spec, q_spec, r_spec, r_spec]

    dq = _tile_call(
        functools.partial(_flash_bwd_dq_kernel, **tile), tiles, b * h, ops,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        in_specs=in_specs(ops),
        out_specs=pl.BlockSpec((1, block_q, d_p), ops.q_block),
        scratch_shapes=[pltpu.VMEM((block_q, d_p), jnp.float32)],
    )(qt, kt, vt, dot, lse, delta)
    # dk/dv: a key/value head's key blocks resident, each over its group's
    # query heads in turn and their live query blocks, which stream: dk
    # and dv come out at the key/value heads, summed over the group in
    # the float32 accumulators
    by_group = dataclasses.replace(ops, by_group=True)
    kv_spec = pl.BlockSpec((1, block_k, d_p), by_group.k_block)
    dk, dv = _tile_call(
        functools.partial(_flash_bwd_dkv_kernel, groups=ops.groups, **tile),
        tiles, b * ops.kv_heads, by_group, key_major=True,
        out_shape=(jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)),
        in_specs=in_specs(by_group),
        out_specs=(kv_spec, kv_spec),
        scratch_shapes=[pltpu.VMEM((block_k, d_p), jnp.float32),
                        pltpu.VMEM((block_k, d_p), jnp.float32)],
    )(qt, kt, vt, dot, lse, delta)
    return (ops.unfold(dq, b)[:, :sq, :, :d],
            ops.unfold(dk, b, kv=True)[:, :sk, :, :d],
            ops.unfold(dv, b, kv=True)[:, :sk, :, :d])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 128,
                    block_k: int = 128, mask=None):
    """Pallas forward + pallas FlashAttention-2 backward (dq and dk/dv
    kernels over the saved logsumexp). Ragged seq lengths and unaligned
    head dims are padded internally (module docstring); callers wanting
    the measured-fastest block config should go through
    ``ops.autotune.auto_flash_attention`` instead of picking blocks.
    ``mask``: a static ``TileMask`` in place of ``causal``; its dead
    tiles are no grid step in any of the three kernels. ``q``
    [b, sq, h, d]; ``k``, ``v`` [b, sk, kv_heads, d] with ``kv_heads``
    dividing ``h``: each serves ``h // kv_heads`` consecutive query heads,
    is read by them and not repeated, and gets its gradient at
    ``kv_heads``."""
    return _flash_fwd(q, k, v, causal, block_q, block_k, mask=mask)


def _named_fwd(q, k, v, causal, block_q, block_k, mask):
    """The forward kernel's two results as both ``custom_vjp`` rules hand
    them to the backward, under ``RESIDUAL_NAMES``."""
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k,
                          return_lse=True, mask=mask)
    return (checkpoint_name(out, RESIDUAL_NAMES[0]),
            checkpoint_name(lse, RESIDUAL_NAMES[1]))


def _fa_fwd(q, k, v, causal, block_q, block_k, mask):
    out, lse = _named_fwd(q, k, v, causal, block_q, block_k, mask)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, mask, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, block_q, block_k,
                      mask=mask)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             block_q: int = 128, block_k: int = 128,
                             mask=None):
    """Like ``flash_attention`` but also returns the per-row logsumexp
    ([b·h, s] fp32). Differentiable in BOTH outputs — the lse cotangent
    folds into the backward kernels' softmax-Jacobian term — which is
    what ring attention needs to merge per-ring-step partial softmaxes
    (ops/ring_attention.py use_flash path)."""
    return _flash_fwd(q, k, v, causal, block_q, block_k, return_lse=True,
                      mask=mask)


def _fal_fwd(q, k, v, causal, block_q, block_k, mask):
    out, lse = _named_fwd(q, k, v, causal, block_q, block_k, mask)
    return (out, lse), (q, k, v, out, lse)


def _fal_bwd(causal, block_q, block_k, mask, res, g):
    q, k, v, o, lse = res
    g_out, g_lse = g
    return _flash_bwd(q, k, v, o, lse, g_out, causal, block_q, block_k,
                      g_lse=g_lse, mask=mask)


flash_attention_with_lse.defvjp(_fal_fwd, _fal_bwd)
