"""Device milliseconds per multiply of the Mosaic kernels, on the busiest
chip."""


def read(r):
    dev, n = r.traced("bench.multiply")
    return None if dev is None else dev.mosaic_s / n * 1e3
