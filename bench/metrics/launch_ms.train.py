"""launch_ms.train: host milliseconds a block from plan to enqueued
dispatch in the engine: the program's ``fl/pack`` (the block's plans
stacked into index arrays), ``fl/put`` (their upload) and ``fl/dispatch``
(the compiled block call until it returns under async dispatch) spans,
over the window's blocks (``bench/spans.py``)."""
from bench import spans


def read(record):
    return spans.ms_per_block(record, ("fl/pack", "fl/put", "fl/dispatch"))
