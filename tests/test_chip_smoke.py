"""chip_smoke.py's refusals, checked where there is no chip.

The smoke itself only runs on a TPU (through the chip tool); what tier-1
can hold is the other half of its contract: off the chip, or with a switch
set that would let something else pass for the compiled chip path, it
exits non-zero within seconds and prints no result.
"""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(env_extra, cwd=ROOT, script=SMOKE):
    env = dict(os.environ)
    for var in ("ZOO_PALLAS_INTERPRET", "ZOO_CPU_FALLBACK", "ZOO_FAULT_PLAN"):
        env.pop(var, None)
    env.update(env_extra)
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    return proc, time.monotonic() - t0


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        if line.lstrip().startswith("{"):
            if json.loads(line).get("ok"):
                return False
    return True


def test_refuses_on_the_cpu_naming_the_platform_it_found():
    proc, dt = _run({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode not in (0, None)
    assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr
    assert _no_result(proc.stdout)
    assert dt < 60.0


@pytest.mark.parametrize("var", ["ZOO_PALLAS_INTERPRET", "ZOO_CPU_FALLBACK",
                                 "ZOO_FAULT_PLAN"])
def test_refuses_a_switch_that_hides_the_device(var):
    """Checked before JAX is touched, so it answers instantly anywhere."""
    proc, _ = _run({"JAX_PLATFORMS": "cpu", var: "1"})
    assert proc.returncode not in (0, None)
    assert var in proc.stderr and _no_result(proc.stdout)


def test_fails_in_a_directory_that_holds_nothing_else(tmp_path):
    """The script alone is not the program: without the package beside it
    there is nothing to smoke, whatever the machine."""
    import shutil
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc, _ = _run({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""},
                   cwd=str(tmp_path), script=str(alone))
    assert proc.returncode not in (0, None)
    assert _no_result(proc.stdout)


def test_no_check_can_be_compiled_away():
    """``python -O`` drops ``assert`` statements: the smoke's checks go
    through ``check()``, which raises."""
    import ast
    with open(SMOKE) as fh:
        tree = ast.parse(fh.read())
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.Assert)]
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.Try)], \
        "a try/except could turn a failed phase into a note"
