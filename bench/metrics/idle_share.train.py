"""idle_share.train: the share of the traced training window in which no
operation ran on a chip (one less the union of the operations' intervals
over the window), the mean over the chips."""


def read(record):
    t = record["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
