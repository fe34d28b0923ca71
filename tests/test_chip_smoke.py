"""chip_smoke.py refuses to run anywhere but on a TPU."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
