"""Without a TPU the measurement path fails: exit code 3, no result."""
import os
import subprocess
import sys

from bench import loader


def test_run_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(loader.BENCH / "run.py"), "--workload",
         "farm20k_c6.websrv_j100k", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"],
        cwd=loader.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "TPU" in p.stderr
