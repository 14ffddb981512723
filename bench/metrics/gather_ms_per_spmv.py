"""Device milliseconds per multiply of the runner's x gather: the ops
under the program's `spmv.x_gather` stage scope (the gather and the
index arithmetic feeding it), on the busiest chip."""

from bench import scopes


def read(r):
    return scopes.stage_ms_per_spmv(r, "spmv.x_gather")
