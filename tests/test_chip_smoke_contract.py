"""chip_smoke.py's device gate: without a TPU it runs nothing.

The script itself only passes on the chip (the builder's chip tool runs it);
what can be pinned here is that a machine without one gets a non-zero exit,
no result line, and no compile — unless the rehearsal switch is given on the
script's own command line.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_cpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode != 0, proc.stdout
    assert proc.stdout.strip() == "", "no phase line and no result line on a CPU"
    # it stopped at the device check, before the trainer was even imported
    assert "not a TPU" in proc.stderr and "Traceback" not in proc.stderr
