"""Readings that the limits of ``correct`` are set from (PERF.md gives
them), on the chip, at a cell's own size:

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 \
        [--control-seeds 4 5 6] \
        [--fault half_batch --fault-seeds 7 8 9] [--precision default]

For each seed it runs the cell's runner with a short window (``--seconds``)
and prints every number it compares by: first as the program gives them
(the lower readings), then with the reference computed in one bfloat16
pass in the program's place (``--control-seeds``), and then with a fault
planted in the program (``FAULTS``). ``--precision`` runs the program at
another matrix precision than the traffic file states: ``default`` is
the program's own one-pass path, the control. Nothing here runs in a
benchmark run. It exits non-zero without a TPU unless ``--cpu`` is given
(the tests use that).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


@contextlib.contextmanager
def _patch(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def unchanged(cell):
    """A training step that returns its state unchanged: the block's
    dispatch hands back the global model it was given."""
    from repro.core import algorithms
    return _patch(algorithms._Planner, "dispatch_block",
                  lambda self, sched, w_glob, lrs, state: w_glob)


def half_batch(cell):
    """Half of every batch left out, the mean taken over the rest: the hop
    gather, where every loss on the fused path gets its batch, hands each
    lane the first half of its rows."""
    from repro.core import local
    gather = local.hop_gather

    def half(images, labels, offsets, row_s, ix, item_shape):
        return gather(images, labels, offsets, row_s,
                      ix[:, :ix.shape[1] // 2], item_shape)

    return _patch(local, "hop_gather", half)


FAULTS = {f.__name__: f for f in (unchanged, half_batch)}


def reading(cell: harness.Cell, seed: int, seconds: float, *,
            control: bool = False, fault: str = "") -> dict:
    """One run of the cell's runner; every number it compares by."""
    ctx = harness.Context(cell, seed, seconds, False, time.perf_counter())
    ctx.control = control
    with FAULTS[fault](cell) if fault else contextlib.nullcontext():
        out = cell.runner.run(ctx)
    return out.record["readings"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", action="append", default=[],
                    choices=sorted(FAULTS))
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--precision", default="")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell.load(args.workload)
    if args.precision:
        cell.traffic["matmul_precision"] = args.precision
    if not args.cpu:
        try:
            harness.require_chips(cell.chips)
        except harness.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
    harness.configure(cell, cache=not args.cpu)
    runs = ([(s, False, "") for s in args.seeds]
            + [(s, True, "") for s in args.control_seeds]
            + [(s, False, f) for f in args.fault for s in args.fault_seeds])
    for seed, control, fault in runs:
        line = {"workload": cell.name, "seed": seed,
                "precision": cell.traffic.get("matmul_precision", "default"),
                "mode": "control" if control else (fault or "program")}
        line["checks"] = reading(cell, seed, args.seconds, control=control,
                                 fault=fault)
        print("calibrate " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
