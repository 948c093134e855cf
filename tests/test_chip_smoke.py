"""``chip_smoke.py`` on the CPU: it refuses to report without a TPU, and
every one of its phases runs end to end at a small size with the Pallas
cell interpreted — so the script cannot rot between chip runs."""
import os
import subprocess
import sys

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_every_phase_rehearses_on_cpu():
    out = chip_smoke.run(chip_smoke.Sizing(
        n_hosts=24, n_intervals=16, eager_intervals=8, pretrain_epochs=2,
        snapshots=2, pallas="interpret"))
    assert out["tier1"]["max_rel"] <= 1e-5
    assert out["kernel_rel"] <= 1e-5
    assert not out["service"]["degraded"]
    assert out["service"]["retrain_failures"] == 0
