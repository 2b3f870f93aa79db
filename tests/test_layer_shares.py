"""One chip's share of a layer whose heads or experts lie on several
chips: the shares of a partition add up to the uncut layer. Four head
shares of a Kimi delta attention layer and of a latent attention layer,
and four expert shares of an expert layer with its shared expert, which
every chip computes alike and which is counted once."""

import numpy as np
import pytest

CHIPS = 4


def _x(hidden=32, seq=24, seed=0):
    import jax.numpy as jnp
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(1, seq, hidden)), jnp.float32)


def _noisy(params, seed=1):
    """Weights away from their initialisation (norm scales off one)."""
    import jax
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.2 * jax.random.normal(key, a.shape)
        for a, key in zip(leaves, keys)])


def _head_share(params, chip, per_head: dict):
    """The parameters of chip ``chip``'s heads: each leaf named in
    ``per_head`` (path -> the axis its heads lie along) sliced to the
    chip's heads; every other leaf whole."""
    import jax

    def cut(path, a):
        name = "/".join(str(k.key) for k in path)
        if name not in per_head:
            return a
        axis = per_head[name]
        n = a.shape[axis] // CHIPS
        return jax.lax.slice_in_dim(a, chip * n, (chip + 1) * n, axis=axis)

    return jax.tree_util.tree_map_with_path(cut, params)


def _check_shares(whole_layer, share_layer, per_head, hidden=32):
    import jax
    x = _x(hidden)
    params = _noisy(whole_layer.init(jax.random.PRNGKey(0), x)["params"])
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(whole_layer.apply)({"params": params}, x)
        share = jax.jit(share_layer.apply)
        parts = sum(share({"params": _head_share(params, c, per_head)}, x)
                    for c in range(CHIPS))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_kda_head_shares_add_up_to_the_layer():
    from analytics_zoo_tpu.ops.kda import KimiDeltaAttention
    heads, d = 8, 8
    columns = {f"{n}/kernel": 1 for n in "qkvfgb"}
    columns.update({f"{n}_conv": 1 for n in "qkv"})
    columns.update({"A_log": 0, "dt_bias": 0, "o/kernel": 0})
    _check_shares(KimiDeltaAttention(heads, d),
                  KimiDeltaAttention(heads // CHIPS, d), columns)


def test_latent_attention_head_shares_add_up_to_the_layer():
    """The latent projection ``kv_a`` and its norm serve every head and
    are whole on every chip."""
    from analytics_zoo_tpu.ops.attention import LatentAttention
    heads = 8
    columns = {"q/kernel": 1, "kv_b/kernel": 1, "gate/kernel": 1,
               "o/kernel": 0}
    _check_shares(LatentAttention(heads, 8, 8, 8, 16, 6e6, 1e-6),
                  LatentAttention(heads // CHIPS, 8, 8, 8, 16, 6e6, 1e-6),
                  columns)


@pytest.mark.parametrize("shared", [0, 20])
def test_expert_shares_with_the_shared_expert_once_add_up_to_the_layer(
        shared):
    """Each chip routes over all 16 experts and computes its 4; the
    shared expert is in every chip's result: the shares minus all but one
    shared expert are the uncut layer."""
    import jax
    from analytics_zoo_tpu.ops.moe import DroplessMoE, GatedMLP

    def layer(held):
        return DroplessMoE(16, 4, 12, held, scale=2.5,
                           n_group=4, topk_group=2, shared_width=shared)

    x = _x(16)
    params = _noisy(layer(None).init(jax.random.PRNGKey(0), x)["params"])
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(layer(None).apply)({"params": params}, x)
        parts = 0
        for c in range(CHIPS):
            held = tuple(range(4 * c, 4 * c + 4))
            share = dict(params, experts={
                n: w[4 * c:4 * c + 4] for n, w in params["experts"].items()})
            parts = parts + jax.jit(layer(held).apply)({"params": share}, x)
        if shared:
            parts = parts - (CHIPS - 1) * GatedMLP(shared).apply(
                {"params": params["shared"]}, x)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
