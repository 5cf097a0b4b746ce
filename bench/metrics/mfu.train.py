"""mfu.train: the training window's required work over its wall time, as
a share of what the cell's chips could do at their peaks.

The least time the chips could take for the work is the larger of
operations / (chips x peak FLOP/s) and bytes / (chips x peak bytes/s);
the share is that time over the window's wall time. The runner counts
the operations and bytes from the shapes (``bench/configs/<config>.py``):
valid lane-steps only, no ghost lanes, padded steps or recompute, plus
each eval's forward.
"""
import json


def read(record):
    if "lane_steps" not in record:
        return None
    chips, peak = record["chips"], record["peak"]
    t_flops = record["flops"] / (chips * peak["flops_per_s"])
    t_bytes = record["bytes"] / (chips * peak["bytes_per_s"])
    print(json.dumps({"roofline_bound": "flops" if t_flops >= t_bytes
                      else "bytes", "flops_s": t_flops, "bytes_s": t_bytes}))
    return 100.0 * max(t_flops, t_bytes) / record["window_s"]
