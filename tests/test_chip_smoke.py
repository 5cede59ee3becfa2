"""``chip_smoke.py`` refuses to run anywhere but on a TPU.

Its verdict line names the device it ran on; a CPU run must never produce
one.  The script is started with the CPU forced, in a child process that
never reaches the TPU library.
"""

import os
import subprocess
import sys
from pathlib import Path

SCRIPT = Path(__file__).resolve().parent.parent / "chip_smoke.py"


def test_chip_smoke_exits_nonzero_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                          cwd=SCRIPT.parent, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
