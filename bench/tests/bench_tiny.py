"""Helpers of the benchmark's tests: cells cut to a size the CPU runs in
seconds. Nothing here touches a TPU."""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402


def tiny_cell(name: str) -> harness.Cell:
    """The cell ``name`` of BENCHMARK.json at a tiny size."""
    cell = harness.Cell.load(name)
    tr = copy.deepcopy(cell.traffic)
    tr["data"].update(train_per_class=20, test_per_class=10)
    tr["fl"].update(num_devices=20, num_edges=5)
    cell.traffic = tr
    return cell


def run(cell: harness.Cell, seed: int, seconds: float = 0.3) -> dict:
    """``harness.run_cell`` without the look for a chip and without the
    persistent compilation cache: the result line."""
    configure = harness.configure
    harness.configure = lambda c, cache=True: configure(c, cache=False)
    try:
        return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                                t0=time.perf_counter())
    finally:
        harness.configure = configure
