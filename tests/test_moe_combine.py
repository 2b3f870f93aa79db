"""The expert layer's combine as pallas kernels (``ops/moe_combine.py``),
interpreted on the CPU, against the ``k`` gathers of ``moe._sum_rows`` it
replaces where it engages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import flash_attention as fa
from analytics_zoo_tpu.ops import moe, moe_combine


def gathers(y, rows, inside, ends):
    """``moe._sum_rows`` with the kernels off: the ``k`` gathers."""
    engages = moe_combine.engages
    moe_combine.engages = lambda y: False
    try:
        return moe._sum_rows(y, rows, inside, ends)
    finally:
        moe_combine.engages = engages


def as_the_chip_sums(y, rows, inside, ends):
    """The gathers as the TPU compiler runs them: the rows' float32 sums,
    rounded once (on the CPU a bfloat16 loop rounds after each add)."""
    return gathers(y.astype(jnp.float32), rows, inside, ends).astype(y.dtype)


def window(n, k, experts, held, start, size, hidden, dtype, seed=0):
    """A window of ``held_expert_ffn``'s at ``n`` tokens routed to ``k`` of
    ``experts`` at random: ``y`` [size, hidden] (rows past the last held
    assignment hold NaN, as rows past ``n_held`` hold whatever a product
    left there), ``rows``, ``inside``, ``ends`` as the layer makes them."""
    rng = np.random.default_rng(seed)
    ids = np.stack([rng.permutation(experts)[:k] for _ in range(n)])
    g = len(held)
    slot = np.full((experts,), g, np.int32)
    slot[list(held)] = np.arange(g)
    group = jnp.asarray(slot[ids].reshape(n * k))
    order = jnp.argsort(group, stable=True)
    position = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
    ends = jnp.cumsum(jnp.sum(group[:, None] == jnp.arange(g), axis=0,
                              dtype=jnp.int32))
    inside = (group < g).reshape(n, k) & (position >= start) \
        & (position < start + size)
    rows = jnp.clip(position - start, 0, size - 1)
    live = int(jnp.clip(ends[-1] - start, 0, size))
    y = jnp.asarray(rng.normal(size=(size, hidden)) * 3, dtype)
    y = jnp.where(jnp.arange(size)[:, None] < live, y, jnp.nan).astype(dtype)
    return y, rows, inside, jnp.clip(ends, start, start + size) - start


def assert_bitwise(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    # blocks of 16 tokens and 16 packed rows: the cases below run over
    # several of each, the last ones partial
    monkeypatch.setattr(moe_combine, "SLOT_BYTES", 1)
    monkeypatch.setattr(moe_combine, "PACK_ROWS", 16)


@pytest.mark.parametrize("dtype,hidden", [(jnp.float32, 1024),
                                          (jnp.bfloat16, 2048)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("start,size", [(0, 301), (40, 170)],
                         ids=["first_window", "second_window"])
def test_the_kernel_is_the_gathers_bit_for_bit(interpreted, k, dtype,
                                               hidden, start, size):
    """200 tokens routed to ``k`` of 32 experts, 12 held: tokens with no
    held assignment among them, held assignments outside the window, a
    window of a size that is a multiple of no block, and NaN in its rows
    past the last held one. Each token's sum to the bit, and finite."""
    held = (0, 3, 5, 8, 11, 13, 17, 20, 22, 26, 29, 31)
    y, rows, inside, ends = window(200, k, 32, held, start, size, hidden,
                                   dtype, seed=k)
    assert (~inside).all(axis=1).any() and inside.any()
    assert moe_combine.engages(y)
    got = moe._sum_rows(y, rows, inside, ends)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert_bitwise(got, as_the_chip_sums(y, rows, inside, ends))
    if dtype == jnp.float32:
        assert_bitwise(got, gathers(y, rows, inside, ends))
    empty = np.asarray(~inside.any(axis=1))
    assert not np.asarray(got, np.float32)[empty].any()


def test_every_assignment_held(interpreted):
    """``g == n_experts``: the window is ``tokens * k`` rows and every
    entry points at one, each row once."""
    n, k = 120, 4
    y, rows, inside, ends = window(n, k, 8, tuple(range(8)), 0, n * k, 2048,
                                   jnp.bfloat16, seed=3)
    assert inside.all()
    assert_bitwise(moe._sum_rows(y, rows, inside, ends),
                   as_the_chip_sums(y, rows, inside, ends))


def test_rows_no_entry_points_at_are_never_read(interpreted):
    """A window larger than the held assignments: its last rows hold NaN
    and no entry points at them; nor at any row a held entry outside the
    window would (the rows are clipped to the window). The sums stay
    finite and equal the gathers', which select those rows away."""
    y, rows, inside, ends = window(100, 8, 16, (1, 2, 3), 0, 400, 2048,
                                   jnp.bfloat16, seed=5)
    assert np.isnan(np.asarray(y[-1], np.float32)).all()
    got = moe._sum_rows(y, rows, inside, ends)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert_bitwise(got, as_the_chip_sums(y, rows, inside, ends))


@pytest.mark.parametrize("hidden,dtype,engages", [
    (2048, jnp.bfloat16, True), (4096, jnp.bfloat16, True),
    (1024, jnp.bfloat16, False), (1024, jnp.float32, True),
    (256, jnp.float32, False), (32, jnp.float32, False),
    (2048, jnp.float16, False)])
def test_it_engages_where_a_row_is_whole_words_and_kernels_run(
        monkeypatch, hidden, dtype, engages):
    y = jnp.zeros((8, hidden), dtype)
    assert not moe_combine.engages(y)                       # on the CPU
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    assert moe_combine.engages(y) == engages
    monkeypatch.setattr(fa, "on_tpu", lambda: False)
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    assert moe_combine.engages(y) == engages


@pytest.mark.parametrize("n,k,words,tokens", [
    (16384, 8, 1024, 128), (16384, 4, 1024, 256), (16384, 8, 2048, 64),
    (16384, 1, 1024, 256), (16384, 3, 1024, 256), (100, 8, 1024, 100)])
def test_a_block_is_four_mib_of_rows_and_at_most_256_tokens(n, k, words,
                                                            tokens):
    """The decoder cells' widths (bfloat16 rows of 2,048 are 1,024 words)
    at their ``k`` of 8 and 4, float32 rows, ``k`` 1 and 3; fewer tokens
    than a block are one."""
    got = moe_combine.block_tokens(n, k, words)
    assert got == tokens
    assert got == n or got % 16 == 0


def _ffn_operands(dtype, n=512, hidden=1024, width=128, experts=8, k=2):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(n, hidden)), dtype)
    ws = [jnp.asarray(rng.normal(size=s) * 0.05, jnp.float32)
          for s in ((experts, hidden, width), (experts, hidden, width),
                    (experts, width, hidden))]
    logits = jnp.asarray(rng.normal(size=(n, experts)), jnp.float32)
    cotangent = jnp.asarray(rng.normal(size=(n, hidden)), dtype)
    return x, ws, logits, cotangent


@pytest.mark.parametrize("held", [(0, 3), tuple(range(8))],
                         ids=["two_held", "all_held"])
def test_held_expert_ffn_value_and_gradient_are_the_gathers(monkeypatch,
                                                            held):
    """One dropless layer's output and its gradient in ``x`` and the three
    weights, float32, kernels against gathers: the same bits. Two of
    eight held, the layer runs two windows (512 of the 1,024 rows, the
    rest under the ``cond``); all held, one window of ``tokens * k``
    rows."""
    calls = []
    x, (w1, w3, w2), logits, cotangent = _ffn_operands(jnp.float32)
    ids, weights = moe.softmax_top_k_routing(logits, 2)
    ws = [w[:len(held)] for w in (w1, w3, w2)]

    def layer(x, w1, w3, w2):
        return moe.held_expert_ffn(x, ids, weights, w1, w3, w2, held, 8)[0]

    def of_both():
        out, pull = jax.vjp(layer, x, *ws)
        return (out,) + pull(cotangent)

    want = of_both()
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(moe_combine, "SLOT_BYTES", 1)
    sum_rows = moe_combine.sum_rows
    monkeypatch.setattr(moe_combine, "sum_rows",
                        lambda *a: calls.append(1) or sum_rows(*a))
    got = of_both()
    # the second window's forward is traced again for its recomputation
    assert len(calls) == (2 if len(held) == 8 else 5)
    for g, w in zip(got, want):
        assert np.asarray(w).any()
        assert_bitwise(g, w)


def test_dropless_moe_keeps_the_gathers_at_a_hidden_of_no_whole_row(
        monkeypatch):
    """At hidden 32 (the CPU tests' tiny decoders) the interpreter forced
    on changes nothing: no kernel is traced, the same bits come out."""
    layer = moe.DroplessMoE(n_experts=8, k=2, d_hidden=16, held=(1, 4))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 12, 32)),
                    jnp.float32)
    params = {"params": layer.init(jax.random.PRNGKey(0), x)["params"]}

    def value_and_grad():
        return jax.value_and_grad(lambda p, x: (layer.apply(
            p, x, mutable=["counters"])[0] ** 2).sum(), argnums=(0, 1))(
                params, x)

    want = value_and_grad()
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")

    def refused(*a):
        raise AssertionError("the combine kernel was traced")

    monkeypatch.setattr(moe_combine, "sum_rows", refused)
    got = value_and_grad()
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert_bitwise(g, w)
