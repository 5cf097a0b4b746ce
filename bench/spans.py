"""The program's span log (``repro.utils.trace``) cut to the measured
window, for the per-layer readers of the program's spans and counters.

The harness's profiler trace keeps only the harness's and the runners'
own spans, so these readers read the log that the program keeps in this
process, on its ``time.perf_counter`` clock. The window's blocks are the
last ``record["blocks"]`` blocks the program planned (a runner plans
nothing after its window); on the log's clock the window runs from the
start of the first of those ``fl/plan`` spans to the end of the last
span of a block (``BLOCK_SPANS``), and an event counts with the part of
it that lies inside. Nothing is read (``None``) where the program keeps
no such log, or where the log no longer holds the window's first block.

The first reader to cut a record prints one earlier line,
``window_spans``: each span's count and seconds in the window, each
counter's sum there, and the process's compile and collector totals.
"""
from __future__ import annotations

import json
from typing import List, Optional, Sequence, Tuple

BLOCK_SPANS = ("fl/plan", "fl/stage", "fl/pack", "fl/put", "fl/dispatch",
               "fl/finish")

Event = Tuple[str, float, float, float]
_last: list = [None, None]      # the record cut last, and its cut


def _log():
    try:
        from repro.utils import trace
    except ImportError:         # a program without the span log
        return None
    return trace


def cut(events: Sequence[Event], blocks: int) -> Optional[List[Event]]:
    """The events of the last ``blocks`` planned blocks, clipped to them;
    ``None`` where fewer than ``blocks`` plans are in ``events``."""
    plans = [e for e in events if e[0] == "fl/plan"]
    if blocks <= 0 or len(plans) < blocks:
        return None
    lo = plans[-blocks][1]
    hi = max(e[2] for e in events if e[0] in BLOCK_SPANS)
    return [(n, max(s, lo), min(e, hi), v) for n, s, e, v in events
            if e >= lo and s <= hi]


def summary(events: Sequence[Event]) -> dict:
    """Per name: a span's count and seconds, a counter's sum."""
    out: dict = {}
    for n, s, e, v in events:
        if n.startswith("fl/"):
            d = out.setdefault(n, {"count": 0, "seconds": 0.0})
            d["count"] += 1
            d["seconds"] += e - s
        else:
            out[n] = out.get(n, 0) + v
    return out


def window(record: dict) -> Optional[List[Event]]:
    """The log's events in the record's window (see the module's doc)."""
    if _last[0] is record:
        return _last[1]
    trace = _log()
    got = None
    if trace is not None and "blocks" in record:
        got = cut(trace.events(), int(record["blocks"]))
    _last[:] = [record, got]
    if got is not None:
        snap = trace.snapshot()
        print(json.dumps({
            "window_spans": summary(got), "blocks": record["blocks"],
            "process_compiles": snap["compiles"],
            "process_gc": {k: v for k, v in snap["counters"].items()
                           if k.startswith("gc")}}), flush=True)
    return got


def ms_per_block(record: dict, names: Sequence[str]) -> Optional[float]:
    """Milliseconds a window block in the spans ``names``."""
    got = window(record)
    if got is None:
        return None
    return 1e3 * sum(e - s for n, s, e, _ in got if n in names) \
        / record["blocks"]


def sum_per_block(record: dict, counter: str) -> Optional[float]:
    """A counter's window sum a block."""
    got = window(record)
    if got is None:
        return None
    return sum(v for n, _, _, v in got if n == counter) / record["blocks"]
