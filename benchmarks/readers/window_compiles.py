"""Lowerings and compilations JAX reported inside the measured window
(``jax.monitoring``; a persistent-cache hit still lowers and counts)."""


def read(cell, run):
    return run["evidence"]["compiles"]["count"]
