"""The command itself: the CPU rehearsal of both cells prints no result
line; without a TPU, or without the program beside it, it fails."""

import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import run as harness

CMD = [sys.executable, os.path.join(harness.ROOT, "benchmarks", "run.py")]
ENV = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}


@pytest.mark.parametrize("cell", ["glm_sparse_fit", "game_fit"])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_no_result_line(cell, trace):
    done = subprocess.run(
        CMD + ["--workload", cell, "--seed", str(2 ** 31 + 11), "--seconds",
               "0.5", "--trace", trace, "--cpu-rehearsal"],
        capture_output=True, text=True, env=ENV, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == ""
    last = done.stderr.strip().splitlines()
    assert last[-1].startswith("cpu rehearsal done") and "correct=True" in last[-1]
    assert last[-2].startswith("cpu compared ")  # the numbers come last


def test_without_a_tpu_it_fails_and_prints_nothing():
    done = subprocess.run(
        CMD + ["--workload", "glm_sparse_fit", "--seed", "1", "--seconds",
               "1", "--trace", "0"],
        capture_output=True, text=True, timeout=600,
        env=dict(ENV, JAX_PLATFORMS="cpu"),
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_without_the_program_it_fails_and_prints_nothing(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(harness.ROOT, "benchmarks"), tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", "glm_sparse_fit", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=600, env=ENV, cwd=tmp_path,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
