"""Adam (Kingma & Ba 2015, algorithm 1, with bias correction) in plain
float32 ``jax.numpy``, over any tree of parameters."""

import jax
import jax.numpy as jnp


def init(params):
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    return {"count": 0, "mu": zeros(), "nu": zeros()}


@jax.jit
def _update(params, grads, mu, nu, count, lr, b1, b2, eps):
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu


def step(params, grads, state, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    count = state["count"] + 1
    f = jnp.float32
    params, mu, nu = _update(params, grads, state["mu"], state["nu"],
                             f(count), f(lr), f(b1), f(b2), f(eps))
    return params, {"count": count, "mu": mu, "nu": nu}
