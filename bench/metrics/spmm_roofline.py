"""Share of the roofline of a k-vector multiply, in percent: the least
time it can take on the cell's chips (`work_spmm.spmm_floor_seconds`:
values, X and Y moved once at peak bandwidth, or the operations at peak,
whichever is longer) over the program's device time per multiply on the
busiest chip."""

from bench import spmm_scopes, work_spmm


def read(r):
    dev, n = r.traced("bench.multiply")
    if dev is None or r.peak is None or dev.program_s <= 0.0 \
            or "vectors" not in r.info or not spmm_scopes.live_ops():
        return None
    floor = work_spmm.spmm_floor_seconds(
        r.info["nnz"], r.info["n_rows"], r.info["n_cols"],
        r.info["vectors"], r.peak, r.chips)
    return 100.0 * floor / (dev.program_s / n)
