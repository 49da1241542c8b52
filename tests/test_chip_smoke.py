"""chip_smoke.py refuses to report a result anywhere but on a TPU: on the
CPU, and in a directory that holds the script without the repository,
it exits non-zero and prints no ``"ok"`` line."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    script = SCRIPT
    if where == "alone":
        script = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, env=env, timeout=300,
                         cwd=os.path.dirname(script))
    assert out.returncode != 0, out.stdout
    assert '"ok"' not in out.stdout, out.stdout
