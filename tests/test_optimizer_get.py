"""``Optimizer.get``: a name, a mapping a configuration file can hold, an
instance or an optax transformation."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from analytics_zoo_tpu.learn.optimizers import (SGD, Adam, Optimizer,
                                                AdamWeightDecay)


def _three_steps(opt: Optimizer):
    tx = opt.to_optax()
    params = {"w": jnp.asarray([1.0, -2.0, 3.0]), "b": jnp.asarray(0.5)}
    state = tx.init(params)
    for k in range(3):
        grads = jax.tree_util.tree_map(lambda p: (k + 1) * 0.1 * p + 0.01,
                                       params)
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
    return params


def test_a_mapping_builds_the_same_transformation_as_the_constructor():
    got = Optimizer.get({"name": "adam", "learningrate": 1e-5})
    assert isinstance(got, Adam) and got.lr == 1e-5
    a, b = _three_steps(got), _three_steps(Adam(learningrate=1e-5))
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    # and not the default rate's
    c = _three_steps(Adam())
    assert np.any(np.asarray(a["w"]) != np.asarray(c["w"]))


def test_a_mapping_takes_every_argument_of_the_class():
    got = Optimizer.get({"name": "Adam", "learningrate": 3e-4, "beta1": 0.8,
                         "beta2": 0.99, "epsilon": 1e-6})
    assert (got.lr, got.b1, got.b2, got.eps) == (3e-4, 0.8, 0.99, 1e-6)
    sgd = Optimizer.get({"name": "sgd", "learningrate": 0.1,
                         "momentum": 0.9})
    assert isinstance(sgd, SGD) and sgd.momentum == 0.9


@pytest.mark.parametrize("bad", [
    {"name": "adamn", "learningrate": 1e-5},        # no such optimizer
    {"learningrate": 1e-5},                         # no name
    {"name": "adam", "lr": 1e-5},                   # no such argument
    {"name": "adam", "learningrate": 1e-5, "momentum": 0.9},
])
def test_an_unknown_name_or_argument_raises(bad):
    with pytest.raises(ValueError):
        Optimizer.get(bad)


def test_a_plain_name_an_instance_and_a_transformation_still_work():
    assert isinstance(Optimizer.get("adam"), Adam)
    assert Optimizer.get("adam").lr == 1e-3
    assert isinstance(Optimizer.get("AdamW"), AdamWeightDecay)
    adam = Adam(learningrate=2e-3)
    assert Optimizer.get(adam) is adam
    tx = optax.sgd(0.1)
    assert Optimizer.get(tx).to_optax() is tx
    with pytest.raises(ValueError, match="unknown optimizer"):
        Optimizer.get("nope")
    with pytest.raises(TypeError):
        Optimizer.get(3)
