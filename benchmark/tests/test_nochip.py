"""Without a TPU the benchmark exits non-zero and prints no result."""

import os
import subprocess
import sys

from harness import registry


def test_cpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = registry.benchmark()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(registry.BENCH, "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=registry.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "not tpu" in proc.stderr
