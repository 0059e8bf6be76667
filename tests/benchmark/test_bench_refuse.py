"""`benchmark/run.py` refuses to measure without a TPU: it exits non-zero
and prints no result line, here on the CPU and in a directory that holds
nothing but BENCHMARK.json and the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import benchtools


def _run(cwd, workload="transfers-sat"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.strip().splitlines()[-1:]:
        try:
            json.loads(line)
        except ValueError:
            return True
        return False
    return True


@pytest.mark.parametrize("workload,says", [
    ("transfers-sat", "no TPU"),
    ("no-such-cell", "no workload"),
])
def test_refuses_without_a_tpu(workload, says):
    p = _run(benchtools.REPO, workload)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert says in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    with open(os.path.join(benchtools.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(benchtools.REPO, "BENCHMARK.json"), tmp_path)
    for rel in bench["paths"]:
        shutil.copytree(os.path.join(benchtools.REPO, rel),
                        os.path.join(tmp_path, rel),
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout)
