"""The command refuses to run without a TPU: non-zero exit, no result."""

import os
import subprocess
import sys

from benchmark import spec


def test_no_tpu_exits_non_zero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         spec.load()["workloads"][0]["name"], "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
