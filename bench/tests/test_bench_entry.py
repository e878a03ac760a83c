"""The command exits non-zero and prints no result without an
accelerator, and in a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

import pytest

import harness


@pytest.mark.parametrize("where", ["no_accelerator", "benchmark_only"])
def test_no_result(where, tmp_path):
    root = harness.ROOT
    if where == "benchmark_only":
        shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
        shutil.copytree(os.path.join(root, "bench"), tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        root = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "paper_coded_static", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "error:" in proc.stderr
