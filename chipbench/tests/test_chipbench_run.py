"""run.py refuses to run without a TPU, and without the program, and
prints no result either way."""

import os
import shutil
import subprocess
import sys

import small
import bench

CELL = bench.benchmark_spec()["workloads"][0]["name"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed", "5000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(small.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(small.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(small.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
