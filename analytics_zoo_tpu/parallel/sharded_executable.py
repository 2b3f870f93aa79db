"""Mesh-sharded model executables — one serving replica, many chips.

`ClusterServing` scales out by adding replicas (consumer-group fan-out,
PR 9); this module scales the *other* axis: a single replica whose model
is too big for one chip dispatches onto a ``ShardedExecutable`` — the
apply function AOT-compiled against a ``jax.sharding.Mesh`` with the
parameters partitioned by a :class:`~analytics_zoo_tpu.parallel.strategy.
ShardingStrategy` (tp / fsdp / dp rules, first match wins). The replica
seam above it (`InferenceModel`, the engine's assembly loop, the bucket
ladder) is unchanged: `ExecutableCache` keys on batch shape/dtype, and a
compiled sharded executable auto-places uncommitted host batches per its
compiled input shardings, so numpy batches from the serve thread hit the
mesh-lowered rungs directly.

Per-shard HBM accounting rides along: :meth:`ShardedExecutable.
shard_hbm_bytes` sums each parameter leaf's addressable shards by
device, publishing ``zoo_shard_hbm_bytes{shard}`` — the gauge that
*proves* no single device holds the full model.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.common import compile_ahead, telemetry
from analytics_zoo_tpu.parallel.strategy import ShardingStrategy


def _canonical(a):
    """Device-canonical host view of one leaf (f64→f32, i64→i32) —
    mirrors mesh.place_on_mesh so sharded params match unsharded ones."""
    if hasattr(a, "sharding"):            # already a committed jax.Array
        return a
    a = np.asarray(a)
    if a.dtype == np.float64:
        a = a.astype(np.float32)
    elif a.dtype == np.int64:
        a = a.astype(np.int32)
    return a


class ShardedExecutable:
    """An apply function + mesh-sharded params behind the cache seam.

    ``__call__(*batch)`` dispatches through a
    :class:`~analytics_zoo_tpu.common.compile_ahead.ExecutableCache`
    whose rungs were warmed with **sharded** avals (params carry their
    ``NamedSharding``, batch avals carry the strategy's batch spec), so
    the hot path never recompiles and never gathers the model onto one
    device.
    """

    def __init__(self, apply_fn, params, strategy="tp", *,
                 param_rules=None, mesh=None, devices=None,
                 name: str = "sharded"):
        import jax

        self.name = name
        self.strategy = ShardingStrategy.parse(strategy,
                                               param_rules=param_rules)
        if mesh is None:
            mesh = self.strategy.build_mesh(devices=devices,
                                            set_default=False)
        self.mesh = mesh
        shardings = self.strategy.param_shardings(params, mesh)
        host = jax.tree_util.tree_map(_canonical, params)
        self.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s), host, shardings)
        self._jitted = telemetry.instrument_jit(apply_fn, name=name)
        self.cache = compile_ahead.ExecutableCache(self._jitted, name=name)
        self._m_shard_hbm = telemetry.get_registry().gauge(
            "zoo_shard_hbm_bytes",
            "Parameter bytes resident per mesh shard (device) — "
            "max(shard) < total proves the model never fits one device",
            ("shard",))
        self.shard_hbm_bytes()

    # ------------------------------------------------------------ avals
    def batch_sharding(self, ndim: int):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, self.strategy.batch_spec(ndim))

    def param_avals(self):
        """Params as avals that carry their shardings, so an AOT build
        lowers to exactly the executable the live dispatch needs."""
        return compile_ahead.tree_avals(self.params)

    def batch_avals(self, spec: Sequence[Tuple], rung: int):
        """Batch avals for one ladder rung, carrying the strategy's
        batch sharding. ``spec`` is the per-sample ``((shape, dtype),
        ...)`` form `InferenceModel` records."""
        import jax

        out = []
        for shape, dtype in spec:
            shp = (int(rung),) + tuple(shape)
            try:
                out.append(jax.ShapeDtypeStruct(
                    shp, dtype, sharding=self.batch_sharding(len(shp))))
            except TypeError:
                out.append(jax.ShapeDtypeStruct(shp, dtype))
        return tuple(out)

    def aval_set(self, spec, rung):
        return (self.param_avals(),) + self.batch_avals(spec, rung)

    # ---------------------------------------------------------- dispatch
    def __call__(self, *xs):
        return self.cache(self.params, *xs)

    def warm(self, spec, rungs, block: bool = True, cpu_also: bool = False):
        todo = [self.aval_set(spec, r) for r in rungs]
        if block:
            for avals in todo:
                self.cache.warm(*avals)
        else:
            self.cache.warm_async(todo, cpu_also=cpu_also)
        return self

    # ------------------------------------------------------ accounting
    @property
    def n_shards(self) -> int:
        return int(self.mesh.devices.size)

    def total_param_bytes(self) -> int:
        import jax
        return int(sum(int(getattr(leaf, "nbytes", 0))
                       for leaf in jax.tree_util.tree_leaves(self.params)))

    def shard_hbm_bytes(self, publish: bool = True) -> Dict[str, int]:
        """Parameter bytes resident on each mesh device, from the live
        arrays' addressable shards — real per-device accounting, not
        ``total / n`` arithmetic."""
        import jax

        totals: Dict[str, int] = {
            str(d.id): 0 for d in self.mesh.devices.flat}
        for leaf in jax.tree_util.tree_leaves(self.params):
            for s in getattr(leaf, "addressable_shards", ()):
                key = str(s.device.id)
                totals[key] = totals.get(key, 0) + int(s.data.nbytes)
        if publish:
            for shard, nbytes in totals.items():
                self._m_shard_hbm.labels(shard=shard).set(nbytes)
        return totals
