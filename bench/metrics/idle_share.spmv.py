"""Percent of the traced window of multiplies in which the chips run no
operation (mean over the cell's chips)."""


def read(r):
    dev, _ = r.traced("bench.multiply")
    if dev is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
