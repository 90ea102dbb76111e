"""Without a TPU the benchmark exits non-zero and prints no result."""
from __future__ import annotations

import os
import subprocess
import sys

from harness_util import BENCH, CHECKOUT


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "criteo-train-search", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
