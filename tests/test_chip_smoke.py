"""The four-chip check (chip_smoke.py) still runs: rehearsed on four
CPU devices, its control flow reaches the end of both phases."""

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_four_chip_check_rehearses_on_four_cpu_devices():
    """``python chip_smoke.py --rehearse`` under four virtual CPU devices
    passes the isolation phase and the dp2 x tp2 step against one device,
    prints no ok line (a rehearsal is not a chip run) and exits 3."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("RTPU_CHAOS", None)   # whatever this worker's tests left set
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"),
         "--rehearse"],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True, timeout=240)
    said = r.stdout[-3000:] + r.stderr[-3000:]
    assert r.returncode == 3, said
    assert "[isolation] phase passed" in r.stdout, said
    assert "[four] phase passed" in r.stdout, said
    assert "chip_smoke: all phases passed" in r.stdout, said
    assert '"ok"' not in r.stdout, said
