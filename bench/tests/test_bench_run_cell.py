"""``run_cell.py`` end to end on the CPU: it refuses to run without a chip
or without the program, a sound run at a small size reads correct, and a
timed path broken underneath reads not correct."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

import run_cell
from harness import cells, faults

ROOT = cells.ROOT
CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]
SMALL = {"n_nodes": 48}


def _run(name, seed=5, seconds=0.2):
    args = run_cell.parse(["--workload", name, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0"])
    return run_cell.run(args, cells.load_cell(name), jax.devices(),
                        sim_overrides=SMALL)


def test_exits_nonzero_without_a_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line = _run(name)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"ticks_per_s", "chunk_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["window"]["compiles_in_window"] == 0


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("kind", faults.FAULTS)
def test_broken_timed_path_is_not_correct(name, kind):
    with faults.planted(kind, cells.load_cell(name).engine):
        line = _run(name)
    assert line["correct"] is False, line["checks"]
    assert line["failed"] > 0
