"""Device milliseconds per multiply of the runner's XLA operations (x pad,
x gather, merge, the stitch across chips): every operation of the
program's device time that is not a Mosaic kernel, on the busiest chip."""


def read(r):
    dev, n = r.traced("bench.multiply")
    return None if dev is None else dev.xla_s / n * 1e3
