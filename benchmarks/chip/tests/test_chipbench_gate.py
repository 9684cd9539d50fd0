"""``run.py`` off a TPU: a non-zero exit that names the platform, and no
result line."""
from __future__ import annotations

import os
import subprocess
import sys

from chipbench_tiny import BENCH_DIR, REPO_DIR


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH_DIR / "run.py"), "--workload", "cnn_dense",
         "--seed", str(2 ** 32 + 1), "--seconds", "1", "--trace", "0"],
        cwd=REPO_DIR, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr
    assert proc.stdout.strip() == ""
