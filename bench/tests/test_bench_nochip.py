"""Without a TPU the benchmark prints no result and exits non-zero, and
so it does in a directory that holds only BENCHMARK.json and bench/."""
from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "planetlab-400.start", "--seed", str(2**31 + 9),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: pathlib.Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_bare_directory_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
