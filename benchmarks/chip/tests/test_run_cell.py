"""run_cell.py refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH

ROOT = os.path.dirname(os.path.dirname(BENCH))
ARGS = ["--workload", "phi4-mini.acc-strict", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_nonzero_off_tpu():
    p = _run(ROOT, os.path.join(BENCH, "run_cell.py"))
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, str(tmp_path / "benchmarks" / "chip" / "run_cell.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
