"""MoE / expert-parallel tests."""

import numpy as np
import pytest

from analytics_zoo_tpu.ops.moe import MoEModule, ep_param_rules, top_k_gating
from analytics_zoo_tpu.parallel import mesh as mesh_lib


class TestGating:
    def test_dispatch_slots_are_exclusive(self):
        import jax
        rng = np.random.RandomState(0)
        logits = rng.randn(32, 4).astype(np.float32)
        dispatch, combine, aux = top_k_gating(
            jax.numpy.asarray(logits), k=2, capacity=16)
        d = np.asarray(dispatch)
        # every (expert, slot) holds at most one token
        assert d.sum(axis=0).max() <= 1.0 + 1e-6
        # every token dispatched to at most k experts
        assert d.sum(axis=(1, 2)).max() <= 2.0 + 1e-6
        assert np.isfinite(float(aux))

    def test_combine_weights_match_gates(self):
        import jax
        import jax.numpy as jnp
        rng = np.random.RandomState(1)
        logits = jnp.asarray(rng.randn(16, 4).astype(np.float32))
        probs = np.asarray(jax.nn.softmax(logits, -1))
        dispatch, combine, _ = top_k_gating(logits, k=1, capacity=16)
        c = np.asarray(combine)
        top1 = probs.argmax(-1)
        for n in range(16):
            got = c[n].sum()
            np.testing.assert_allclose(got, probs[n, top1[n]], rtol=1e-5)

    def test_capacity_drops_overflow(self):
        import jax.numpy as jnp
        # all tokens want expert 0; capacity 2 → only 2 dispatched
        logits = jnp.asarray(np.tile([10.0, 0.0], (8, 1)).astype(np.float32))
        dispatch, _, _ = top_k_gating(logits, k=1, capacity=2)
        assert float(np.asarray(dispatch)[:, 0].sum()) == 2.0


class TestMoEModule:
    def test_forward_shapes_and_grad(self):
        import jax
        m = MoEModule(n_experts=4, d_model=8, d_hidden=16, k=2)
        x = np.random.RandomState(0).randn(4, 6, 8).astype(np.float32)
        variables = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(variables, x)
        assert out.shape == x.shape

        def loss(params):
            y = m.apply({"params": params}, x)
            return (y ** 2).mean()

        g = jax.grad(loss)(variables["params"])
        for leaf in jax.tree_util.tree_leaves(g):
            assert np.isfinite(np.asarray(leaf)).all()
        # gating and at least some experts receive signal
        assert np.abs(np.asarray(g["gate"])).max() > 0
        assert np.abs(np.asarray(g["w1"])).max() > 0

    def test_aux_loss_consumed_by_train_step(self, orca_ctx):
        """Regression: the sown load-balance loss used to be dropped — MoE
        trained with zero balancing. The reported loss must include the
        weighted aux term, and model_state must not accumulate it."""
        import flax.linen as nn
        from analytics_zoo_tpu.learn.estimator import Estimator

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                h = MoEModule(n_experts=4, d_model=8, d_hidden=16,
                              name="moe")(x, train=train)
                return nn.Dense(2)(h)

        rng = np.random.RandomState(1)
        x = rng.randn(64, 8).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)

        def run(aux_w):
            est = Estimator.from_flax(
                model=Net(), loss="sparse_categorical_crossentropy_logits",
                optimizer="sgd", sample_input=x[:2], seed=0,
                aux_loss_weight=aux_w)
            h = est.fit((x, y), epochs=2, batch_size=32, shuffle=False)
            return est, h

        est0, h0 = run(0.0)
        est1, h1 = run(1.0)
        # aux term is positive → the optimized objective differs
        assert h1["loss"][0] > h0["loss"][0]
        # aux_loss never leaks into persistent state (sow would grow it
        # every step otherwise)
        assert "aux_loss" not in est1._state["model_state"]
        assert "aux_loss" not in est1.adapter.model_state
        # with weight, gate gradients include the balance signal → gate
        # params diverge from the aux-free run
        g0 = np.asarray(est0._state["params"]["moe"]["gate"])
        g1 = np.asarray(est1._state["params"]["moe"]["gate"])
        assert not np.allclose(g0, g1)

    def test_ep_train_step_emits_all_to_all(self, orca_ctx):
        """The expert-sharded einsums must lower to cross-device collectives
        (all-to-all resharding tokens batch→expert layout) over the mesh."""
        import flax.linen as nn
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from analytics_zoo_tpu.learn.estimator import Estimator

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                h = MoEModule(n_experts=8, d_model=8, d_hidden=16,
                              name="moe")(x, train=train)
                return nn.Dense(2)(h)

        rng = np.random.RandomState(2)
        x = rng.randn(32, 8).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        est = Estimator.from_flax(
            model=Net(), loss="sparse_categorical_crossentropy_logits",
            optimizer="adam", sample_input=x[:2],
            strategy="dp2,ep4", param_rules=ep_param_rules())
        est._build_train_step()
        mesh = est._ensure_mesh()
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        ys = jax.device_put(y, NamedSharding(mesh, P("data")))
        hlo = est._train_step.lower(est._state, xs, ys).compile().as_text()
        assert ("all-to-all" in hlo) or ("all-gather" in hlo), \
            "no cross-device collective for the expert dimension"

    def test_expert_parallel_training(self, orca_ctx):
        """End-to-end ep training: expert weights sharded over 'expert'."""
        import flax.linen as nn
        import jax
        from analytics_zoo_tpu.learn.estimator import Estimator

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                h = nn.Dense(8)(x)
                h = MoEModule(n_experts=4, d_model=8, d_hidden=16,
                              name="moe")(h, train=train)
                return nn.Dense(2)(h)

        rng = np.random.RandomState(0)
        x = rng.randn(64, 8).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        est = Estimator.from_flax(
            model=Net(), loss="sparse_categorical_crossentropy_logits",
            optimizer="adam", sample_input=x[:2],
            strategy="dp2,ep4", param_rules=ep_param_rules())
        h1 = est.fit((x, y), epochs=1, batch_size=16)
        h8 = est.fit((x, y), epochs=8, batch_size=16)
        assert h8["loss"][-1] < h1["loss"][0]
        w1 = est._state["params"]["moe"]["w1"]
        assert "expert" in str(w1.sharding.spec), w1.sharding.spec


# ------------------------------------- grouped routing and a shared expert

def _get_topk_indices(scores, bias, n_group, topk_group, k):
    """``transformers``' ``deepseek_v3`` ``DeepseekV3MoE.get_topk_indices``
    transcribed in numpy, a token at a time."""
    ids = []
    for s in scores + bias:
        groups = s.reshape(n_group, -1)
        group_scores = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = np.argsort(-group_scores, kind="stable")[:topk_group]
        mask = np.zeros(n_group, bool)
        mask[kept] = True
        choice = np.where(np.repeat(mask, groups.shape[1]), s, 0.0)
        ids.append(np.argsort(-choice, kind="stable")[:k])
    return np.asarray(ids)


@pytest.mark.parametrize("n_group,topk_group,k", [(8, 4, 8), (4, 1, 2),
                                                  (4, 4, 3)])
def test_grouped_routing_is_deepseek_v3s(n_group, topk_group, k):
    """The experts ``sigmoid_top_k_routing`` picks with ``n_group`` > 1
    are ``get_topk_indices``'s and its weights ``s / sum * scale`` (its
    1e-6 against that code's 1e-20 within the tolerance): no expert
    outside the ``topk_group`` best groups is chosen."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.moe import sigmoid_top_k_routing
    rng = np.random.default_rng(n_group * 10 + k)
    E = 64
    logits = rng.normal(size=(96, E)).astype(np.float32)
    bias = rng.normal(scale=0.1, size=(E,)).astype(np.float32)
    ids, weights = sigmoid_top_k_routing(
        jnp.asarray(logits), jnp.asarray(bias), k, scale=2.5,
        n_group=n_group, topk_group=topk_group)
    scores = 1 / (1 + np.exp(-logits.astype(np.float64)))
    want = _get_topk_indices(scores, bias, n_group, topk_group, k)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1),
                                  np.sort(want, -1))
    chosen = np.take_along_axis(scores, np.asarray(ids), -1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.5 * chosen / (chosen.sum(-1, keepdims=True)
                                             + 1e-20), rtol=1e-5)
    groups_used = np.unique(np.asarray(ids) // (E // n_group), axis=None)
    assert all(len(set(row // (E // n_group))) <= topk_group
               for row in np.asarray(ids))
    assert len(groups_used) > 1


def test_keeping_every_group_is_the_ungrouped_choice():
    """``topk_group == n_group`` masks nothing: the grouped selection is
    the one over all experts, ids and weights alike."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.moe import sigmoid_top_k_routing
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    bias = jnp.asarray(rng.normal(scale=0.1, size=(32,)), jnp.float32)
    whole = sigmoid_top_k_routing(logits, bias, 4, scale=2.5)
    grouped = sigmoid_top_k_routing(logits, bias, 4, scale=2.5, n_group=4,
                                    topk_group=4)
    for a, b in zip(whole, grouped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _moe(held, shared, experts=16, k=4, n_group=4, topk_group=2):
    from analytics_zoo_tpu.ops.moe import DroplessMoE
    return DroplessMoE(experts, k, 12, held, scale=2.5,
                       n_group=n_group, topk_group=topk_group,
                       shared_width=shared)


def test_a_shared_expert_is_added_once_beside_the_routed_ones():
    """``shared_width``: a gated expert under ``moe/shared`` that every
    token goes through, unweighted: the layer with it is the layer
    without it plus that block of the same weights."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.moe import GatedMLP
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 24, 16)),
                    jnp.float32)
    layer = _moe(None, 20)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    assert set(params) == {"router", "experts", "shared"}
    assert params["shared"]["w1"]["kernel"].shape == (16, 20)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x)
        routed = _moe(None, 0).apply(
            {"params": {k: v for k, v in params.items() if k != "shared"}},
            x)
        shared = GatedMLP(20).apply({"params": params["shared"]}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(routed + shared),
                               rtol=1e-5, atol=1e-6)
