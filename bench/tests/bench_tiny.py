"""Helpers of the benchmark's tests: cells cut to a size the CPU runs in
seconds. Nothing here touches a TPU.

Each cell's cut comes from its own files: its traffic file's ``tiny``
object, which every traffic file holds, and its configuration file's, where
there is one, each deep-merged over its file. As a script it runs a cell's
four CPU cases and prints, as one JSON line, whether each came out correct:

    python3 bench/tests/bench_tiny.py --workload <cell>
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402


def merged(base: dict, tiny: dict, where: str) -> dict:
    """``base`` with ``tiny`` deep-merged over it: an object merges into
    the object under the same key, any other value replaces. A key that
    ``base`` lacks is refused, since a cut may only cut."""
    out = copy.deepcopy(base)
    for key, value in tiny.items():
        if key not in base:
            raise KeyError(f"{where}: 'tiny' sets {key!r}, which the file "
                           "does not have")
        if isinstance(value, dict) and isinstance(base[key], dict):
            out[key] = merged(base[key], value, f"{where}: {key}")
        else:
            out[key] = copy.deepcopy(value)
    return out


def _cut(body: dict, path: Path, required: bool) -> dict:
    if "tiny" not in body:
        if required:
            raise ValueError(f"{path} has no 'tiny' object, the cut its cell "
                             "runs at in the CPU tests")
        return body
    base = {k: v for k, v in body.items() if k != "tiny"}
    return merged(base, body["tiny"], str(path))


def tiny_cell(name: str) -> harness.Cell:
    """The cell ``name`` of BENCHMARK.json at its tiny cut."""
    spec = harness.read_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.Cell.load(name, spec)
    w = next(w for w in spec["workloads"] if w["name"] == name)
    cell.traffic = _cut(cell.traffic, harness.BENCH / "traffic"
                        / f"{w['traffic']}.json", required=True)
    cell.config = _cut(cell.config, harness.BENCH / "configs"
                       / f"{w['config']}.json", required=False)
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


def control_reading(cell: harness.Cell, seed: int = 5) -> dict:
    """Every number the cell compares by, with the control (the reference
    with every product from one bfloat16 pass) in the program's place."""
    from bench import calibrate
    harness.configure(cell, cache=False)
    return calibrate.reading(cell, seed, 0.2, control=True)


def cases(name: str) -> dict:
    """Whether the cell's tiny run, its control and each planted fault
    come out correct."""
    from bench import calibrate
    cell = tiny_cell(name)
    got, lim = control_reading(cell), cell.traffic["limits"]
    out = {"run": run(tiny_cell(name), 2**31 + 11)["correct"],
           "control": all(got[k] <= lim[k] for k in lim)}
    for fault in calibrate.FAULTS:
        cell = tiny_cell(name)
        with calibrate.FAULTS[fault](cell):
            out[fault] = run(cell, 7)["correct"]
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    print(json.dumps(cases(ap.parse_args().workload)), flush=True)
