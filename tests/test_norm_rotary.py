"""The q/k norm and rotary positions as one kernel each way
(``ops/norm_rotary.py``), interpreted on the CPU, against the XLA chain
``nn.RMSNorm`` -> ``rotary_embedding`` it replaces where it engages."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import attention as attention_lib
from analytics_zoo_tpu.ops import flash_attention as fa
from analytics_zoo_tpu.ops import norm_rotary

D, THETA, EPS = 128, 1e6, 1e-6


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def chain(x, scale, heads, positions):
    """What ``GroupedQueryAttention`` runs off the op: ``x`` [b, s,
    heads·d] through the norm module and ``rotary_embedding``, in the
    norm's dtype, back to rows."""
    b, s, _ = x.shape
    norm = nn.RMSNorm(epsilon=EPS, dtype=x.dtype)
    y = norm.apply({"params": {"scale": scale}}, x.reshape(b, s, heads, D))
    return attention_lib.rotary_embedding(y, THETA, positions).reshape(
        b, s, heads * D)


def operands(heads, seq, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, seq, heads * D)) * 3, dtype)
    scale = jnp.asarray(1 + 0.2 * rng.normal(size=D), jnp.float32)
    cotangent = jnp.asarray(rng.normal(size=x.shape), dtype)
    return x, scale, cotangent


def of_both(fn, x, scale, cotangent):
    """``fn``'s value and its gradient in ``x`` and ``scale`` under the
    cotangent, all float32."""
    y, pull = jax.vjp(fn, x, scale)
    dx, dscale = pull(cotangent)
    return [np.asarray(a, np.float32) for a in (y, dx, dscale)]


# the forward rounds once where the chain rounds after the norm and again
# after the rotation: bfloat16 agrees to that rounding, float32 to its own
TOLERANCE = {jnp.float32: (2e-6, 2e-6, 2e-6), jnp.bfloat16: (6e-3, 2e-2, 2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("positions", ["default", "repeated"])
@pytest.mark.parametrize("heads", [32, 4])
def test_the_op_is_the_norm_and_rotary_chain(monkeypatch, heads, positions,
                                            dtype):
    """Forward, ``dx`` and ``dscale`` at 32 and 4 heads of 128, at
    0..seq-1 and at block diffusion's ``[0..L-1 ; 0..L-1]``, over 40 rows
    in blocks of 16: the last block runs past the sequence."""
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(norm_rotary, "BLOCK_BYTES", 1)      # 16 rows
    seq = 40
    pos = None if positions == "default" else np.tile(np.arange(seq // 2), 2)
    x, scale, cotangent = operands(heads, seq, dtype)
    table = norm_rotary.rotary_table(seq, D, THETA, pos)
    got = of_both(lambda x, s: norm_rotary.norm_rotary(
        x, s, table, heads, EPS), x, scale, cotangent)
    want = of_both(lambda x, s: chain(x, s, heads, pos), x, scale, cotangent)
    for name, g, w, tol in zip(("y", "dx", "dscale"), got, want,
                               TOLERANCE[dtype]):
        assert np.isfinite(g).all(), name
        assert rel(g, w) < tol, (name, rel(g, w))


def test_the_partial_block_adds_nothing_to_dscale(monkeypatch):
    """The same rows in one block and in blocks of 16 over 40: the same
    ``dscale`` to float32's rounding, and ``dx`` to the bit."""
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    x, scale, cotangent = operands(4, 40, jnp.float32, seed=3)
    table = norm_rotary.rotary_table(40, D, THETA)

    def in_blocks_of(block_bytes):
        monkeypatch.setattr(norm_rotary, "BLOCK_BYTES", block_bytes)
        assert norm_rotary._block_rows(40, 4 * D, 4) \
            == (16 if block_bytes == 1 else 40)
        return of_both(lambda x, s: norm_rotary.norm_rotary(
            x, s, table, 4, EPS), x, scale, cotangent)

    whole, blocked = in_blocks_of(1 << 20), in_blocks_of(1)
    np.testing.assert_array_equal(whole[0], blocked[0])
    np.testing.assert_array_equal(whole[1], blocked[1])
    assert rel(blocked[2], whole[2]) < 1e-6


def test_the_table_holds_the_rotary_angles():
    pos = np.tile(np.arange(6), 2)
    table = np.asarray(norm_rotary.rotary_table(12, 8, 100.0, pos))
    # a one-hot row per lane rotated: its cosines and sines come back
    x = jnp.broadcast_to(jnp.eye(8, dtype=jnp.float32)[0], (1, 12, 1, 8))
    y = np.asarray(attention_lib.rotary_embedding(x, 100.0, pos))[0, :, 0]
    np.testing.assert_allclose(table[:, 0], y[:, 0], rtol=1e-6)
    np.testing.assert_allclose(table[:, 4], y[:, 4], rtol=1e-6)
    np.testing.assert_array_equal(table[:6], table[6:])


@pytest.mark.parametrize("heads,dtype,rows", [
    (32, jnp.bfloat16, 256), (4, jnp.bfloat16, 512), (1, jnp.float32, 512),
    (64, jnp.float32, 64)])
def test_a_block_is_two_mib_of_rows_and_at_most_512(heads, dtype, rows):
    assert norm_rotary._block_rows(16384, heads * D,
                                   jnp.dtype(dtype).itemsize) == rows
    assert norm_rotary._block_rows(40, heads * D,
                                   jnp.dtype(dtype).itemsize) == 40


@pytest.mark.parametrize("head_dim", [64, 128, 192, 256])
def test_one_rule_picks_rows_for_the_kernels_and_the_op(monkeypatch,
                                                         head_dim):
    q = jnp.zeros((1, 8, 4, head_dim))
    k = jnp.zeros((1, 8, 2, head_dim))
    rows = fa.rows_layout(head_dim)
    assert rows == (head_dim % 128 == 0)
    assert fa._operands(q, k).rows == rows
    assert not norm_rotary.engages(head_dim)             # on the CPU
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    assert norm_rotary.engages(head_dim) == rows


def _layer_and_input(dtype):
    layer = attention_lib.GroupedQueryAttention(4, 2, D, THETA, EPS,
                                                dtype=dtype)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 24, 48)),
                    jnp.float32)
    return layer, x


def test_the_parameter_tree_is_the_chains(monkeypatch):
    """``q_norm/scale`` and ``k_norm/scale`` under the same names, shapes,
    dtypes and values whichever side runs."""
    layer, x = _layer_and_input(None)
    chain_params = layer.init(jax.random.PRNGKey(0), x)
    for name in ("q_norm", "k_norm"):
        assert chain_params["params"][name]["scale"].shape == (D,)
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(norm_rotary, "engages", lambda head_dim: True)
    op_params = layer.init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_structure(op_params) \
        == jax.tree_util.tree_structure(chain_params)
    for got, want in zip(jax.tree_util.tree_leaves(op_params),
                         jax.tree_util.tree_leaves(chain_params)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_layer_with_the_op_is_the_layer_with_the_chain(monkeypatch, dtype):
    """One ``GroupedQueryAttention`` at head 128, the op forced on (the
    interpreted kernels), against the same layer and weights on the XLA
    chain: output and every gradient."""
    layer, x = _layer_and_input(dtype)
    params = layer.init(jax.random.PRNGKey(1), x)
    params = jax.tree_util.tree_map(
        lambda p: p * 1.3 if p.ndim == 1 else p, params)

    def value_and_grads(params, x):
        def loss(params, x):
            return (layer.apply(params, x).astype(jnp.float32) ** 2).sum()
        return loss(params, x), jax.grad(loss, argnums=(0, 1))(params, x)

    want = value_and_grads(params, x)
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(norm_rotary, "engages", lambda head_dim: True)
    got = value_and_grads(params, x)
    tol = 1e-5 if dtype is None else 3e-2
    leaves = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(leaves, jax.tree_util.tree_leaves(got)):
        assert np.asarray(w, np.float32).any(), path
        assert rel(np.asarray(g, np.float32), np.asarray(w, np.float32)) \
            < tol, path
