"""Share of the roofline, in percent: the least time a multiply can take
on the cell's chips (floor bytes over peak bandwidth; the operation bound
is three orders smaller) over the program's device time per multiply on
the busiest chip."""

from bench import work


def read(r):
    dev, n = r.traced("bench.multiply")
    if dev is None or r.peak is None or dev.program_s <= 0.0:
        return None
    floor = work.spmv_floor_seconds(r.info["nnz"], r.info["n_rows"],
                                    r.info["n_cols"], r.peak, r.chips)
    return 100.0 * floor / (dev.program_s / n)
