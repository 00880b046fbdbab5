"""A whole run with the timed path broken underneath must come out not
correct; a sound run correct.  The harness's look for a chip is skipped and
the cells run at a small size on the CPU (Pallas kernels interpreted)."""

import os
import subprocess
import sys

import pytest

from benchlib import common as C
from benchlib import harness as H

ROOT = C.ROOT

SMALL_LENET = {"rows_per_call": 8, "chunks": 8}


def small_run(workload, fault=None, control=False, seed=2 ** 31 + 17):
    cell = C.load_cell(workload)
    cell.traffic.update(SMALL_LENET)
    run = H.Run(cell, seed, 1.5, False, fault=fault)
    run.control = control
    H.drive(run)
    return run, H.finish(run)


@pytest.mark.parametrize("workload,fault,correct", [
    ("lenet_recipe_b8", None, True),
    ("lenet_recipe_b8", "unchanged", False),
    ("lenet_recipe_b8", "half_batch", False),
])
def test_fault_fails_sound_passes(workload, fault, correct):
    _run, res = small_run(workload, fault)
    assert res["correct"] is correct, res["checks"]
    assert list(res)[-1] == "checks"


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "lenet_recipe_b8", "--seed", "5",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_only_benchmark_files_no_result(tmp_path):
    """In a directory holding only BENCHMARK.json and benchmarks/chip the
    command exits non-zero and prints no result (no program to run)."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "lenet_recipe_b8", "--seed", "5",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
