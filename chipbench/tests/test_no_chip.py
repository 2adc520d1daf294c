"""Without a TPU the benchmark exits non-zero before any work and prints
no result."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_exits_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "sage-reddit-commrand", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr
