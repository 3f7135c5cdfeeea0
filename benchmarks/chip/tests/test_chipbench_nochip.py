"""Without a TPU, run.py exits non-zero and prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import chipbench_tiny as T


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mistral-7b-16l.code", "--seed", str(2 ** 33 + 1), "--seconds",
         "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_tpu():
    p = _run(T.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(T.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(T.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
