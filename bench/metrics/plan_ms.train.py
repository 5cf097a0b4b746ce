"""plan_ms.train: host milliseconds a block in the planner, the program's
``fl/plan`` span (``_Planner.plan_schedule``: the batch plans of every
ring visit, drawn on the host), over the window's blocks
(``bench/spans.py``)."""
from bench import spans


def read(record):
    return spans.ms_per_block(record, ("fl/plan",))
