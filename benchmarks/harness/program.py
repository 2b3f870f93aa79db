"""The conventions of the system's ``Estimator`` that a reference has to
follow to see the rows, the dropout masks and the optimizer's moments the
timed path saw. Each is the program's rule restated, with where it lives;
a PR that changes the rule there fails ``correct`` here and has to bring
the new rule with it (a ``benchmark`` PR).
"""

import numpy as np

#: ``JaxEstimator(seed=0)``: the default, which the cells run
ESTIMATOR_SEED = 0


def build_estimator(module, loss: str, optimizer: str, params, sample):
    """``Estimator.from_flax`` — the factory users call — with the weights
    it drew replaced, before the first step reads them, by the seeded ones
    the reference also gets: fine-tuning from given weights. (Handing the
    weights to ``FlaxModelAdapter(params=...)`` directly skips the probe
    that learns whether the module takes ``train``, and such an estimator
    trains without dropout: PERF.md, Open questions.)"""
    from analytics_zoo_tpu.learn.estimator import Estimator
    est = Estimator.from_flax(model=module, loss=loss, optimizer=optimizer,
                              sample_input=sample, seed=ESTIMATOR_SEED)
    est.adapter.params = params
    return est


def epoch_order(n: int, epoch: int, seed: int = ESTIMATOR_SEED):
    """Row order of one shuffled epoch (``data/dataset.iter_batches``)."""
    order = np.arange(n)
    np.random.default_rng((seed * 100003 + epoch) & 0x7FFFFFFF).shuffle(order)
    return order


def step_key(step: int, seed: int = ESTIMATOR_SEED):
    """The dropout key of optimizer step ``step`` (0-based):
    ``fold_in(PRNGKey(seed + 17), state['step'])`` in
    ``JaxEstimator._build_train_step``."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed + 17), step)


def first_moment(est):
    """Adam's first moment from the estimator's optimizer state, as a tree
    shaped like the parameters."""
    import jax
    for part in jax.tree_util.tree_leaves(
            est._state["opt_state"], is_leaf=lambda s: hasattr(s, "mu")):
        if hasattr(part, "mu"):
            return part.mu
    raise LookupError("no first moment (mu) in the optimizer state")


def parameters(est):
    return est._state["params"]


def release(est):
    """Drop the estimator's device state so the reference has the chip."""
    est._state = None
    est._train_step = None
    est.adapter.params = None
