"""chip_smoke.py refuses to run, and prints no result, without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_cpu(tmp_path, where):
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":         # the script without the program beside it
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr
