"""The driver's dry-run entry point, run as the subprocess the driver runs.

(``benchmark/run.py`` and ``chip_smoke.py`` on a machine without a chip are
covered in tests/test_chip_smoke.py.)
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_scale_leg_cheap_shape():
    """The reshard-restore scale leg (the 8→32 north-star proxy in the
    driver artifact) at its cheap 4→8 shape: save on a 4-device mesh,
    restore onto 8 (dp2×fsdp2×tp2), params bitwise equal, continued loss
    matching the control. Keeps the evidence path itself under test."""
    from easydl_tpu.utils.env import cpu_subprocess_env

    env = cpu_subprocess_env(8)
    env["EASYDL_DRYRUN_CHILD"] = "1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"),
         "--dryrun-scale", "4", "8"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "reshard 4->8 OK" in proc.stdout, proc.stdout
    assert "8dev OK" in proc.stdout, proc.stdout
