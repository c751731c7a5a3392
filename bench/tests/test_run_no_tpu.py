"""bench/run.py exits non-zero and prints no result without a TPU, or
without the program beside it."""
import os
import shutil
import subprocess
import sys

from benchtest_support import ROOT


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "sim-pr-fig8", "--seed",
         "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    p = _run(ROOT, "bench/run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bench_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "bench/run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
