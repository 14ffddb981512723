"""Host milliseconds that block each PageRank iteration: the traced
window less the device's busy time, over the iterations in it (operand
derivation, fingerprinting, the per-iteration copy of y to the host,
the stepper's update)."""


def read(r):
    dev, _ = r.traced("bench.query")
    iters = r.counts.get("iterations", 0)
    if dev is None or not iters:
        return None
    return (r.trace.window_s - r.trace.busy_s) / iters * 1e3
