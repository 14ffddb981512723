"""Percent of the traced window of PageRank queries in which the chip
runs no operation."""


def read(r):
    dev, _ = r.traced("bench.query")
    if dev is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
