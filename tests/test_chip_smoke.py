"""``chip_smoke.py`` refuses to report a result without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    script = SCRIPT
    if where == "alone":        # a directory with nothing else of the repo
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
