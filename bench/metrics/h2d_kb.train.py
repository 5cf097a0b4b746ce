"""h2d_kb.train: KiB a block the data plane uploads, the program's
``h2d_bytes`` counter (each block's index plans, masks, rates and
weights, as ``LocalTrainer.h2d_bytes`` counts them), over the window's
blocks (``bench/spans.py``)."""
from bench import spans


def read(record):
    b = spans.sum_per_block(record, "h2d_bytes")
    return None if b is None else b / 1024.0
