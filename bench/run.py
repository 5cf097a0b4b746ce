"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json`` with its
counts in ``<config>.py`` and its plain reference in
``bench/reference/<config>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``), whose ``runner`` key names the entry
point ``bench/runners/<runner>.py``. The runner loads and warms up,
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and returns its numbers. With ``--trace 1`` the
window runs under the profiler and each per-layer metric of the cell is
read by ``bench/metrics/<metric>.py`` from the run's record.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
then ``checks``: every number compared, beside its limit. The same checks
are the last lines of stderr. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()    # set-up is counted from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.Cell.load(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t0=T0)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
