"""Context bootstrap + global configuration.

TPU-native analog of the reference's context layer:

- ``OrcaContext`` config singleton — ref ``pyzoo/zoo/orca/common.py:21-124``
  (``OrcaContextMeta``: pandas read backend, eager mode, ``train_data_store``,
  shard size).
- ``init_orca_context`` / ``stop_orca_context`` — ref
  ``pyzoo/zoo/orca/common.py:148-255``. Where the reference boots a SparkContext
  (+ optionally a Ray cluster inside Spark executors,
  ``pyzoo/zoo/ray/raycontext.py``), we discover the local TPU devices (or a
  multi-host JAX distributed runtime over DCN) and stand up the default
  ``jax.sharding.Mesh`` that every Estimator trains over.

Cluster modes:

- ``"local"``  — single process, all locally-visible devices (TPU chips or
  ``--xla_force_host_platform_device_count`` virtual CPU devices).
- ``"multihost"`` / ``"tpu_pod"`` — calls ``jax.distributed.initialize`` with a
  coordinator address; replaces the reference's init_spark_on_yarn/k8s
  launchers (``pyzoo/zoo/common/nncontext.py:56,199``). The mesh then spans all
  processes' devices, with collectives riding ICI within a slice and DCN
  across slices.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
import warnings
from typing import Optional, Sequence

logger = logging.getLogger(__name__)

_active_context: Optional["ZooTpuContext"] = None
# guards the init/stop transitions of _active_context: frontend handler
# threads read the context while the main thread (or its atexit hook)
# swaps it
_context_lock = threading.Lock()


class OrcaContextMeta(type):
    """Class-property-style global knobs (ref pyzoo/zoo/orca/common.py:21-122)."""

    _eager_mode = True
    _pandas_read_backend = "pandas"
    _serialize_data_creator = False
    _train_data_store = "DRAM"
    _shard_size = None
    _default_matmul_precision = "bfloat16"
    _checkpoint_max_to_keep = 5

    @property
    def pandas_read_backend(cls):
        """'pandas' or 'arrow' (ref 'spark' backend is JVM-only)."""
        return cls._pandas_read_backend

    @pandas_read_backend.setter
    def pandas_read_backend(cls, value):
        value = value.lower()
        assert value in ("pandas", "arrow"), "pandas_read_backend must be 'pandas' or 'arrow'"
        cls._pandas_read_backend = value

    @property
    def serialize_data_creator(cls):
        return cls._serialize_data_creator

    @serialize_data_creator.setter
    def serialize_data_creator(cls, value):
        assert isinstance(value, bool)
        cls._serialize_data_creator = value

    @property
    def train_data_store(cls):
        """Dataset cache tier: DRAM | DISK_n (ref FeatureSet.scala DRAM/PMEM/DISK_n).

        On TPU hosts there is no Optane PMEM; the analog tiers are host DRAM
        (default) and ``DISK_n`` (keep 1/n of shards resident, stream the rest
        from disk spill — ref zoo/.../feature/FeatureSet.scala:556).
        """
        return cls._train_data_store

    @train_data_store.setter
    def train_data_store(cls, value):
        value = value.upper()
        assert value == "DRAM" or value.startswith(("DISK_", "NATIVE_")), \
            "train_data_store must be 'DRAM', 'DISK_n' or 'NATIVE_n'"
        cls._train_data_store = value

    @property
    def shard_size(cls):
        """Target rows per shard for XShards readers (ref common.py:96-110)."""
        return cls._shard_size

    @shard_size.setter
    def shard_size(cls, value):
        if value is not None:
            assert isinstance(value, int) and value > 0
        cls._shard_size = value

    @property
    def default_matmul_precision(cls):
        """TPU MXU precision for dense math: 'bfloat16'|'tensorfloat32'|'float32'."""
        return cls._default_matmul_precision

    @default_matmul_precision.setter
    def default_matmul_precision(cls, value):
        assert value in ("bfloat16", "tensorfloat32", "float32")
        cls._default_matmul_precision = value

    @property
    def checkpoint_max_to_keep(cls):
        return cls._checkpoint_max_to_keep

    @checkpoint_max_to_keep.setter
    def checkpoint_max_to_keep(cls, value):
        assert isinstance(value, int) and value > 0
        cls._checkpoint_max_to_keep = value


class OrcaContext(metaclass=OrcaContextMeta):
    """Global configuration singleton (ref pyzoo/zoo/orca/common.py:21)."""

    @staticmethod
    def get_context() -> "ZooTpuContext":
        if _active_context is None:
            raise RuntimeError(
                "No active context. Call init_orca_context() first.")
        return _active_context

    @staticmethod
    def get_mesh():
        return OrcaContext.get_context().mesh


class ZooTpuContext:
    """Holds the device topology + default mesh for this process.

    Replaces the SparkContext/RayContext pair the reference threads through
    every API (ref pyzoo/zoo/orca/common.py:126-146 get_spark_context /
    get_ray_context).
    """

    def __init__(self, cluster_mode: str, mesh, num_processes: int,
                 process_index: int):
        self.cluster_mode = cluster_mode
        self.mesh = mesh
        self.num_processes = num_processes
        self.process_index = process_index

    @property
    def devices(self):
        import jax
        return jax.devices()

    @property
    def local_devices(self):
        import jax
        return jax.local_devices()

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    def __repr__(self):
        return (f"ZooTpuContext(mode={self.cluster_mode!r}, "
                f"devices={self.num_devices}, mesh={self.mesh})")


def _sanitize_host_env():
    """Env hygiene before JAX initializes (analog of the reference's MKL/OMP
    env fixing, ref pyzoo/zoo/ray/raycontext.py:105-116)."""
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "3")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")


def init_orca_context(cluster_mode: str = "local",
                      mesh_axes: Optional[Sequence[str]] = None,
                      mesh_shape: Optional[Sequence[int]] = None,
                      coordinator_address: Optional[str] = None,
                      num_processes: Optional[int] = None,
                      process_id: Optional[int] = None,
                      **kwargs) -> ZooTpuContext:
    """Initialise the TPU runtime + default mesh.

    Ref API: ``init_orca_context(cluster_mode, cores, memory, ...)``
    (pyzoo/zoo/orca/common.py:148). Spark/Ray resource kwargs (cores, memory,
    num_nodes...) are accepted and ignored with a warning so reference
    user code ports over unchanged.

    Args:
        cluster_mode: "local" (default) or "multihost"/"tpu_pod".
        mesh_axes / mesh_shape: default mesh layout, e.g. axes
            ``("data", "model")`` shape ``(4, 2)``. Defaults to a 1-D
            ``("data",)`` mesh over all devices.
        coordinator_address, num_processes, process_id: multi-host bootstrap
            (jax.distributed over DCN).
    """
    global _active_context
    if _active_context is not None:
        warnings.warn("init_orca_context called twice; returning existing context")
        return _active_context

    legacy = {k: v for k, v in kwargs.items()
              if k in ("cores", "memory", "num_nodes", "init_ray_on_spark",
                       "conda_name", "extra_python_lib", "penv_archive")}
    if legacy:
        warnings.warn(f"Spark/Ray-era kwargs ignored on TPU backend: {sorted(legacy)}")

    _sanitize_host_env()
    import jax

    # before this process's first compile, so every jit after it —
    # module.init included — lands in the persistent cache
    from analytics_zoo_tpu.common.compile_ahead import (
        configure_persistent_cache,
    )
    configure_persistent_cache()
    # likewise before the first compile: set-up's lowerings, compiles and
    # persistent-cache lookups are counted in the registry from here on
    from analytics_zoo_tpu.common import telemetry
    telemetry.install_compile_counters()

    if cluster_mode in ("multihost", "tpu_pod"):
        if coordinator_address:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id)
        else:
            # On real TPU pods (and other auto-discoverable clusters) JAX
            # infers the coordinator from the environment; elsewhere this
            # fails — surface what the caller must provide. Explicit
            # num_processes/process_id still win over auto-detection.
            try:
                jax.distributed.initialize(num_processes=num_processes,
                                           process_id=process_id)
            except Exception as e:
                raise ValueError(
                    f"cluster_mode={cluster_mode!r}: coordinator "
                    "auto-discovery failed — outside a TPU pod / managed "
                    "cluster pass coordinator_address='host0:port', "
                    f"num_processes and process_id explicitly ({e})") from e
    elif cluster_mode != "local":
        # Accept the reference's mode names so ported scripts still run
        # single-process (ref nncontext.py dispatches yarn/k8s/standalone).
        warnings.warn(f"cluster_mode={cluster_mode!r} has no TPU analog; "
                      f"running in local mode")
        cluster_mode = "local"

    jax.config.update("jax_default_matmul_precision",
                      OrcaContext.default_matmul_precision)

    from analytics_zoo_tpu.parallel.mesh import build_mesh
    mesh = build_mesh(axes=mesh_axes, shape=mesh_shape)

    with _context_lock:
        _active_context = ZooTpuContext(
            cluster_mode=cluster_mode,
            mesh=mesh,
            num_processes=jax.process_count(),
            process_index=jax.process_index())
    atexit.register(stop_orca_context)
    logger.info("Initialized %r", _active_context)
    return _active_context


def stop_orca_context():
    """Tear down the context (ref pyzoo/zoo/orca/common.py:242-255)."""
    global _active_context
    if _active_context is None:
        return
    from analytics_zoo_tpu.parallel import mesh as _mesh_mod
    with _context_lock:
        _mesh_mod._default_mesh = None
        _active_context = None
