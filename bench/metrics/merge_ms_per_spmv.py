"""Device milliseconds per multiply of the runner's merge: the ops under
the program's `spmv.merge` stage scope (the stripe reduce, the
segment-sum carry merge with its sort, HYB's join, the final slices), on
the busiest chip."""

from bench import scopes


def read(r):
    return scopes.stage_ms_per_spmv(r, "spmv.merge")
