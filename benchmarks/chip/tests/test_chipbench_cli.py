"""The command as the driver starts it: no result without a TPU, and
none from a directory that holds only the benchmark's own files."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from chipbench_fixtures import REPO

ARGS = ["--workload", "osm200m-syrmi.sosd-uniform", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def start(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_the_command_refuses_a_cpu_before_it_builds():
    p = start(REPO)
    assert p.returncode != 0
    assert "nothing was built" in p.stderr
    assert '"phase": "setup"' not in p.stdout and '"correct"' not in p.stdout


def test_the_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmarks" / "chip", tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = start(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
