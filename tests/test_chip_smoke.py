"""`chip_smoke.py` must refuse to report success anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("alone", [False, True], ids=["in_repo", "script_alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """On a CPU backend the device check stops the run before any phase;
    copied away from the repository the script cannot import the program.
    Either way it exits nonzero and prints no ``ok`` line."""
    script = SCRIPT
    if alone:
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("REPRO_KERNEL_IMPL", None)
    out = subprocess.run(
        [sys.executable, str(script), "--rows", "4096"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0, out.stdout
    assert '"ok"' not in out.stdout
    assert '"phase"' not in out.stdout
    if not alone:
        assert "no TPU" in out.stderr
