"""The arithmetic that decides ``correct``: each number compared beside a
limit of its own, and the verdict."""

import math
import statistics


def relative_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def worst_leaf_gap(got_norms: dict, want_norms: dict, skip=()) -> tuple:
    """Largest gap between the program's and the reference's norm of a
    leaf, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns (gap, leaf name)."""
    names = [k for k in want_norms if k not in skip]
    median = statistics.median(want_norms[k] for k in names)
    worst, where = 0.0, None
    for k in names:
        gap = abs(got_norms[k] - want_norms[k]) / max(want_norms[k], median)
        if not math.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def median_leaf_gap(got_norms: dict, want_norms: dict, skip=()) -> float:
    """The median leaf's gap, by the same measure: steadier from seed to
    seed than the worst leaf's."""
    names = [k for k in want_norms if k not in skip]
    median = statistics.median(want_norms[k] for k in names)
    return statistics.median(
        abs(got_norms[k] - want_norms[k]) / max(want_norms[k], median)
        for k in names)


def relative_difference(got_tree, want_tree) -> tuple:
    """(whole-tree, median-leaf) norm of the difference over the norm of
    the reference: first order in rounding noise, which a gap of norms is
    not. ``got_tree`` may live on the host; one leaf at a time goes to the
    device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b))

    got = jax.tree_util.tree_leaves(got_tree)
    want = jax.tree_util.tree_leaves(want_tree)
    pairs = [tuple(float(v) for v in sums(jnp.asarray(a), b))
             for a, b in zip(got, want)]
    whole = math.sqrt(sum(d for d, _ in pairs) / sum(w for _, w in pairs))
    leaves = [math.sqrt(d / w) for d, w in pairs if w > 0]
    return whole, statistics.median(leaves)


class Checks:
    """Numbers compared, each with its limit. A number that is not finite,
    or a limit that is missing, fails."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.rows = []          # (name, value, limit)

    def add(self, name: str, value: float):
        self.rows.append((name, float(value), self.limits.get(name)))

    @staticmethod
    def _ok(value: float, limit) -> bool:
        return limit is not None and math.isfinite(value) and value <= limit

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            self._ok(v, lim) for _, v, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def lines(self) -> list:
        return [f"compared {n}: {v!r} limit {lim!r} "
                f"{'ok' if self._ok(v, lim) else 'FAILED'}"
                for n, v, lim in self.rows]
