"""``bench/run.py`` refuses, with a nonzero exit and no result line, on a
machine without a TPU, for an unknown cell, and in a checkout that holds
only the benchmark's own files."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent


def _run(cwd: Path, *args) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _no_result(r):
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_refuses_without_a_tpu():
    r = _run(REPO, "--workload", "lib-expdecay-16k", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    _no_result(r)
    assert "no TPU" in r.stderr


def test_refuses_an_unknown_cell():
    r = _run(REPO, "--workload", "no-such-cell", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    _no_result(r)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_refuses_with_only_the_benchmark_files(tmp_path, trace):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, "--workload", "lib-expdecay-16k", "--seed", "3",
             "--seconds", "1", "--trace", trace)
    _no_result(r)
    assert "src/repro" in r.stderr
