"""gc_ms.train: host milliseconds a block in pauses of Python's collector
of generation 1 or 2, the program's ``fl/gc`` spans, over the window's
blocks (``bench/spans.py``). Generation-0 pauses are not spans; the
``window_spans`` line gives the process's collector totals."""
from bench import spans


def read(record):
    return spans.ms_per_block(record, ("fl/gc",))
