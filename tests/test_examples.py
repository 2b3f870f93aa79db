"""Smoke-run the examples (the reference runs its example scripts in CI,
pyzoo/zoo/examples/run-example-test*.sh — same idea)."""

import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")

# distributed_training sets its own virtual-device env; the others inherit
# the test env (CPU platform via conftest env vars)
ALL = ["recommendation_ncf.py", "anomaly_detection.py",
       "autots_forecast.py", "cluster_serving.py", "torch_migration.py",
       "distributed_training.py", "dogs_vs_cats_transfer.py",
       "sentiment_analysis.py", "vae.py", "fraud_detection.py",
       "image_similarity.py", "wide_and_deep.py", "object_detection.py",
       "image_augmentation.py", "model_inference.py",
       "automl_hp_search.py", "qa_ranker.py", "multihost_launch.py",
       "image_classification_serving.py", "block_diffusion_training.py"]

# the heavyweight end-to-end examples (multi-process launches, real
# training loops: 10-25s each on 1 core) run in the examples lane only
_SLOW = {"distributed_training.py", "autots_forecast.py",
         "object_detection.py", "multihost_launch.py"}


@pytest.mark.parametrize(
    "script", [pytest.param(s, marks=pytest.mark.slow) if s in _SLOW else s
               for s in ALL])
def test_example_runs(script):
    # the examples lane is a CPU lane: a child never takes a chip the
    # test process (or anything else on the host) may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    launcher = (
        "import runpy, sys; "
        "sys.argv = [sys.argv[1]]; "  # argparse-using examples see no args
        "runpy.run_path(sys.argv[0], run_name='__main__')")
    proc = subprocess.run(
        [sys.executable, "-c", launcher, os.path.join(EXAMPLES, script)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, (
        f"{script} failed:\nstdout:\n{proc.stdout[-2000:]}\n"
        f"stderr:\n{proc.stderr[-2000:]}")
