"""The expert layer's combine as pallas kernels: for every token, the sum
of the window rows its held assignments sit at.

``held_expert_ffn`` computes its experts over a window of rows sorted by
held expert and, inside an expert, by token; it hands each token back the
sum of its rows (``moe._sum_rows``: the forward of ``_put_rows`` and the
backward of ``_take_rows``). Written in XLA that sum is ``k`` gathers,
each a whole ``[tokens, hidden]`` copy written and read back, though few
of a token's ``k`` assignments fall on the experts a layer holds.
:func:`sum_rows` copies from HBM only the rows an assignment points at,
and writes each token's row once. Two launches:

- ``_pack_rows_kernel`` writes the window once as ``[rows * tiles, 128]``
  uint32, each row whole 32-bit tiles of its own (``tiles`` = 8 rows of
  128 words at hidden 2,048 in bfloat16: a row's first half in the low 16
  bits of its words, its second half in the high 16; a float32 row as its
  bits). The chip keeps a ``[rows, hidden]`` array in tiles of 8 rows (16
  of a 16-bit type, two to a word), and a DMA moves whole tiles: one row
  of it is no slice a DMA can take.
- ``_sum_rows_kernel`` walks the tokens in blocks of ``block_tokens``. The
  rows a block's held entries point at are one run of consecutive window
  rows for each held expert; the kernel copies the next block's runs into
  one of two staging buffers (whole chunks of ``CHUNK_ROWS`` and pieces
  of a power of two rows: a DMA's size is static) while it sums this
  block's. No row that no entry points at is read: the window's rows
  past the last held one hold whatever a product left there. The sums
  are float32, a token's rows added in the order of ``j`` (the i-th held
  entries of all the block's tokens, then the (i+1)-th), and each token's
  sum is rounded once to the rows' dtype: what XLA's fusion of the
  gathers computes, to the bit.

The tables that steer the kernel (``_tables``: each block's runs, and its
tokens ordered by how many rows they hold with the staging row of each)
are worked in XLA on the tokens in the lanes, and go to SMEM a block at a
time through ``BlockSpec``s: the whole table would not fit.

Where it runs: :func:`engages` — the pallas kernels run (a TPU, or the
interpreter forced) and a packed row is whole tiles: bfloat16 at a hidden
size that is a multiple of 2,048, float32 at a multiple of 1,024. Anywhere
else ``moe._sum_rows`` keeps its gathers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import flash_attention

LANES = 128
#: rows of a 32-bit tile: a packed row is a whole number of them
TILE_ROWS = 8
#: VMEM of one of the sum kernel's two staging buffers: the most rows a
#: block of tokens can point at, ``k`` a token, 4 KiB each at hidden 2,048
#: in bfloat16 (128 tokens at ``k`` 8, 256 at ``k`` 4)
SLOT_BYTES = 4 << 20
#: the most tokens a block takes, whatever its row: the output block and
#: the float32 sums grow with the tokens alone
MAX_TOKENS = 256
#: rows of a block of the pack kernel
PACK_ROWS = 512
#: rows of a whole chunk of a run the sum kernel copies in one DMA (a
#: run's remainder goes in pieces of a power of two rows)
CHUNK_ROWS = 8
#: the scoped VMEM each launch may take: the chip's own limit, stated so
#: that a compile for a described chip refuses what the chip refuses
VMEM_BYTES = 16 << 20
#: the dtypes whose rows the kernels take, and how many go to a word
_PACKED = {jnp.dtype(jnp.bfloat16): 2, jnp.dtype(jnp.float32): 1}


def _words(y) -> int:
    """uint32 words of one of ``y``'s rows, packed."""
    return y.shape[-1] // _PACKED[jnp.dtype(y.dtype)]


def engages(y) -> bool:
    """Whether ``moe._sum_rows`` sums ``y`` [rows, hidden]'s rows through
    :func:`sum_rows`."""
    dtype = jnp.dtype(y.dtype)
    return ((flash_attention.on_tpu() or flash_attention.pallas_interpret())
            and dtype in _PACKED and y.ndim == 2
            and y.shape[-1] % (LANES * TILE_ROWS * _PACKED[dtype]) == 0)


def block_tokens(n: int, k: int, words: int) -> int:
    """Tokens of a block of the sum kernel: the most rows they can point
    at fill ``SLOT_BYTES``, at most ``MAX_TOKENS``; a multiple of 16, or all
    ``n`` tokens."""
    tokens = min(MAX_TOKENS, max(16, SLOT_BYTES // (k * words * 4) // 16 * 16))
    return n if n <= tokens else tokens


def _pack_rows_kernel(y_ref, o_ref):
    import jax.experimental.pallas as pl
    rows = y_ref.shape[0]
    tiles = o_ref.shape[0] // rows
    words = tiles * LANES
    for g in range(tiles):
        lanes = slice(g * LANES, (g + 1) * LANES)
        if y_ref.dtype == jnp.float32:
            w = jax.lax.bitcast_convert_type(y_ref[:, lanes], jnp.uint32)
        else:
            low, high = (jax.lax.bitcast_convert_type(
                y_ref[:, slice(h * words + lanes.start, h * words
                               + lanes.stop)], jnp.uint16).astype(jnp.uint32)
                for h in (0, 1))
            w = low | (high << 16)
        # row r's words g*128 .. g*128+127 go to row r * tiles + g
        o_ref[pl.ds(g, rows, stride=tiles), :] = w


def _pack(y, block: int, interpret: bool):
    """``y`` [rows, hidden] as ``[rows * tiles, 128]`` uint32: each row
    packed to whole 32-bit tiles of its own (a bfloat16 row's first half
    in the low 16 bits of its words, its second half in the high 16), in
    blocks of ``block`` rows."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, hidden = y.shape
    tiles = _words(y) // LANES
    return pl.pallas_call(
        _pack_rows_kernel,
        out_shape=jax.ShapeDtypeStruct((rows * tiles, LANES), jnp.uint32),
        grid=(pl.cdiv(rows, block),),
        in_specs=[pl.BlockSpec((block, hidden), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block * tiles, LANES), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret)(y)


def _sum_rows_kernel(first_ref, next_ref, now_ref, lists_ref, rows_hbm,
                     out_ref, stage, sums, sems, *, k: int, tokens: int,
                     width: int, groups: int, chunk: int, halves: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    i, blocks = pl.program_id(0), pl.num_programs(0)
    tiles = stage.shape[1] // (k * tokens)

    def word(ref, at):
        return ref[at // LANES, at % LANES]

    def each_piece(runs_ref, slot, act):
        # the block's runs of window rows, one a held group, each cut into
        # whole chunks of ``chunk`` rows and a remainder of a power of two
        # rows each: a DMA takes a static size
        def piece(lo, at, size):
            act(pltpu.make_async_copy(
                rows_hbm.at[pl.ds(lo * tiles, size * tiles)],
                stage.at[slot, pl.ds(at * tiles, size * tiles)],
                sems.at[slot]))

        def run(e, at):
            lo = word(runs_ref, e)
            n = word(runs_ref, -(-groups // LANES) * LANES + e)

            def whole(c, carry):
                piece(lo + c * chunk, at + c * chunk, chunk)
                return carry

            jax.lax.fori_loop(0, n // chunk, whole, 0)
            for bit in range(chunk.bit_length() - 1):
                size = 1 << bit
                done = n >> (bit + 1) << (bit + 1)

                @pl.when((n & size) != 0)
                def _():
                    piece(lo + done, at + done, size)
            return at + n

        jax.lax.fori_loop(0, groups, run, 0)

    slot = i % 2

    @pl.when(i == 0)
    def _():
        each_piece(first_ref, 0, lambda dma: dma.start())

    @pl.when(i + 1 < blocks)
    def _():
        each_piece(next_ref, 1 - slot, lambda dma: dma.start())

    each_piece(now_ref, slot, lambda dma: dma.wait())

    parts = 2 if halves else 1
    sums[...] = jnp.zeros(sums.shape, sums.dtype)

    def adder(nth, assign):
        # the nth held entries of the tokens at places p: each token's row
        # added to its sums (nth 0: set where the token holds all k, as
        # the gathers' sum then starts from its first term; +0 + row
        # otherwise, as their cleared terms leave it)
        def add(p, carry):
            at = pl.multiple_of(word(lists_ref, width + p) * tiles, TILE_ROWS)
            row = word(lists_ref, (nth + 2) * width + p)
            w = stage[slot, pl.ds(pl.multiple_of(row * tiles, TILE_ROWS),
                                  tiles), :]
            for h, q in enumerate(
                    (w << 16, w & jnp.uint32(0xFFFF0000)) if halves else (w,)):
                value = jax.lax.bitcast_convert_type(q, jnp.float32)
                if assign:
                    sums[h, pl.ds(at, tiles), :] = value
                else:
                    sums[h, pl.ds(at, tiles), :] += value
            return carry
        return add

    # the block's tokens come most held first: the first word(nth) of them
    # hold an nth entry
    held_all = word(lists_ref, k - 1)
    jax.lax.fori_loop(0, held_all, adder(0, True), 0)
    jax.lax.fori_loop(held_all, word(lists_ref, 0), adder(0, False), 0)
    for nth in range(1, k):
        jax.lax.fori_loop(0, word(lists_ref, nth), adder(nth, False), 0)
    words = tiles * LANES
    for h in range(parts):
        for g in range(tiles):
            at = h * words + g * LANES
            # the tokens' words g*128 .. g*128+127, a row each
            out_ref[:, at:at + LANES] = sums[h, pl.ds(
                g, tokens, stride=tiles), :].astype(out_ref.dtype)


def _tiled(words, blocks: int):
    """``words`` [blocks, ...] int32 as rows of 128, each block's whole
    tiles of 8 rows: what a block of SMEM takes."""
    words = words.reshape(blocks, -1)
    words = jnp.pad(words, ((0, 0), (0, -words.shape[1] % (8 * LANES))))
    return words.reshape(-1, LANES)


def _tables(table, ends, tokens: int):
    """The two SMEM tables of each block of tokens, and the width of a
    row of the second.

    ``runs``: the window rows the block's held entries point at are one
    run of consecutive rows for each held group (the window is sorted by
    group, then by token): ``[2, groups]``, each run's first row, then its
    length, in rows of whole 128 words. The runs land one after another
    in the block's staging buffer. ``lists``: ``[k + 2, width]`` (``tokens``
    to a whole row of 128): how many of the block's tokens hold an i-th
    entry, for i = 0 .. k-1; the tokens, most held first (stably), so that
    those are the first; and for each i the staging row of the i-th held
    entry (in the order of ``j``) of the token at each place. Worked on
    the tokens in the lanes."""
    n_pad, k = table.shape
    blocks = n_pad // tokens
    groups = ends.shape[0]
    rows = table.T                                          # [k, n]
    held = rows >= 0
    group = jnp.sum(rows[None] >= ends[:, None, None], axis=0,
                    dtype=jnp.int32)
    member = (held & (group == jnp.arange(groups)[:, None, None])).reshape(
        groups, k, blocks, tokens)
    length = jnp.sum(member, axis=(1, 3), dtype=jnp.int32)  # [groups, blocks]
    first = jnp.min(jnp.where(member, rows.reshape(1, k, blocks, tokens),
                              jnp.iinfo(jnp.int32).max), axis=(1, 3))
    first = jnp.where(length > 0, first, 0)
    staged = jnp.cumsum(length, axis=0, dtype=jnp.int32) - length
    stage_row = rows + jnp.sum(jnp.where(
        member, (staged - first)[:, None, :, None], 0), axis=0).reshape(
            k, n_pad)
    runs = jnp.stack([first, length]).transpose(2, 0, 1)  # [blocks, 2, g]
    runs = jnp.pad(runs, ((0, 0), (0, 0), (0, -groups % LANES)))
    # each token's held entries first, in the order of j; the tokens of a
    # block by how many they hold, most first (stable): the tokens that
    # hold an i-th entry are then the first n_i
    rank = jnp.cumsum(held, axis=0, dtype=jnp.int32) - 1
    listed = jnp.sum(jnp.where(                              # [i, j, n]
        held & (rank == jnp.arange(k)[:, None, None]), stage_row, 0), axis=1)
    count = jnp.sum(held, axis=0, dtype=jnp.int32).reshape(blocks, tokens)
    t = jnp.arange(tokens, dtype=jnp.int32)
    before = (count[:, None, :] > count[:, :, None]) | (
        (count[:, None, :] == count[:, :, None]) & (t[None, :] < t[:, None]))
    place = jnp.sum(before, axis=2, dtype=jnp.int32)         # [blocks, t]
    # the permutation as a product with its one-hot matrix: one term of
    # each sum is 1 x an integer under 2^24, exact in float32
    at = (place[:, None, :] == t[None, :, None]).astype(jnp.float32)
    order, listed = (jnp.einsum(
        "bpt,...bt->...bp", at, v.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
        for v in (jnp.broadcast_to(t, (blocks, tokens)),
                  listed.reshape(k, blocks, tokens)))       # [k, blocks, p]
    having = jnp.sum(count > jnp.arange(k)[:, None, None], axis=2,
                     dtype=jnp.int32)                        # [k, blocks]
    width = -(-tokens // LANES) * LANES
    lists = jnp.concatenate([
        jnp.pad(having.T, ((0, 0), (0, width - k)))[:, None],
        jnp.pad(order, ((0, 0), (0, width - tokens)))[:, None],
        jnp.pad(listed.transpose(1, 0, 2),
                ((0, 0), (0, 0), (0, width - tokens)))], axis=1)
    return _tiled(runs, blocks), _tiled(lists, blocks), width


def sum_rows(y, table, ends):
    """``[n, hidden]``: for each of the ``n`` tokens of ``table`` [n, k]
    int32 the sum of the rows of ``y`` [rows, hidden] its entries point
    at, an entry of -1 pointing at none; summed in float32 in the order of
    the entries, rounded once to ``y``'s dtype. ``ends`` [groups]: where
    each group of ``y``'s rows ends, the rows sorted by group and, inside
    a group, by token. Not differentiable: the callers' custom VJPs take
    its gradient."""
    n, k = table.shape
    tokens = block_tokens(n, k, _words(y))
    return _sum_rows_call(
        y, table, ends, tokens=tokens, pack_rows=min(y.shape[0], PACK_ROWS),
        chunk=min(CHUNK_ROWS, 1 << (min(tokens * k, y.shape[0]).bit_length()
                                    - 1)),
        interpret=flash_attention.pallas_interpret())


# one trace and one lowering for every call at the same shapes: a step
# calls it twice a window in each of its sparse layers
@functools.partial(jax.jit, static_argnames=("tokens", "pack_rows", "chunk",
                                             "interpret"))
def _sum_rows_call(y, table, ends, *, tokens: int, pack_rows: int,
                   chunk: int, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, k = table.shape
    hidden = y.shape[-1]
    words = _words(y)
    blocks = pl.cdiv(n, tokens)
    table = jnp.pad(table.astype(jnp.int32),
                    ((0, blocks * tokens - n), (0, 0)), constant_values=-1)
    groups = ends.shape[0]
    runs, lists, width = _tables(table, ends.astype(jnp.int32), tokens)

    def smem(tables, index):
        return pl.BlockSpec((tables.shape[0] // blocks, LANES), index,
                            memory_space=pltpu.SMEM)

    halves = _PACKED[jnp.dtype(y.dtype)] == 2
    return pl.pallas_call(
        functools.partial(_sum_rows_kernel, k=k, tokens=tokens, width=width,
                          groups=groups, chunk=chunk, halves=halves),
        out_shape=jax.ShapeDtypeStruct((n, hidden), y.dtype),
        grid=(blocks,),
        in_specs=[smem(runs, lambda i: (0, 0)),
                  smem(runs, lambda i: (jnp.minimum(i + 1, blocks - 1), 0)),
                  smem(runs, lambda i: (i, 0)),
                  smem(lists, lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tokens, hidden), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, k * tokens * words // LANES, LANES), jnp.uint32),
            pltpu.VMEM((2 if halves else 1, tokens * words // LANES, LANES),
                       jnp.float32),
            pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret)(runs, runs, runs, lists,
                             _pack(y, pack_rows, interpret))
