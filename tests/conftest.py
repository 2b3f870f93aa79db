"""Test bootstrap: fake an 8-chip TPU slice with virtual CPU devices.

Mirrors the reference's test strategy (SURVEY.md §4): Spark ``local[n]``
simulated multi-node; here ``--xla_force_host_platform_device_count=8``
simulates an 8-device mesh so every sharding/collective path runs for real.
Must run before jax is imported anywhere.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# The repo root must be importable when tests run from a subdir.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_context():
    yield
    from analytics_zoo_tpu.common import context as ctx
    ctx.stop_orca_context()


@pytest.fixture
def orca_ctx():
    from analytics_zoo_tpu import init_orca_context
    return init_orca_context(cluster_mode="local")
