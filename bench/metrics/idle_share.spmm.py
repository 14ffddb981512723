"""Percent of the traced window of k-vector multiplies in which the chips
run no operation (mean over the cell's chips)."""


def read(r):
    dev, _ = r.traced("bench.multiply")
    if dev is None or "vectors" not in r.info:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
