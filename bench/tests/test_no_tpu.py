"""The command refuses to run without a TPU: nonzero exit, no result."""
import os
import subprocess
import sys

from bench.tests.conftest import ROOT


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vit16.ga_islands",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "TPU" in p.stderr
