"""Without a TPU, or away from the program's checkout, a run exits
non-zero and prints no result line."""
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "train.ckpt", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    return p.returncode, p.stdout


def test_no_tpu_no_result():
    rc, out = _run(harness.ROOT)
    assert rc != 0
    assert '"correct"' not in out


def test_only_the_benchmark_files_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, out = _run(str(tmp_path))
    assert rc != 0
    assert '"correct"' not in out
