"""A measuring run that finds no TPU exits non-zero and prints no result."""
import os
import subprocess
import sys

from benchmarks.harness import spec


def test_run_py_off_the_chip_exits_2_with_empty_stdout():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "gpt3-1.3b.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "not 'tpu'" in p.stderr
