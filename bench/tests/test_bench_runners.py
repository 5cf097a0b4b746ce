"""Each runner at a tiny size on the CPU: the entry refuses to run
without a TPU, and a sound run is correct."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT, harness, run, tiny_cell

CELLS = [w["name"] for w in harness.read_json(ROOT / "BENCHMARK.json")["workloads"]]


def _entry(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("workload", CELLS)
def test_entry_exits_nonzero_without_a_tpu(workload):
    p = _entry(ROOT, workload)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not p.stdout.strip()


def test_entry_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _entry(tmp_path, CELLS[0])
    assert p.returncode != 0 and not p.stdout.strip()


def _line(cell, seed=7, **kw):
    line = run(cell, seed, **kw)
    assert list(line)[-1] == "checks"
    return line


@pytest.mark.parametrize("name", CELLS)
def test_tiny_run_is_correct(name):
    cell = tiny_cell(name)
    line = _line(cell, seed=2**31 + 11)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    json.dumps(line)
