"""Device milliseconds per k-vector multiply of the SpMM runner's XLA
operations (X's row gather under `spmm.x_gather`, the output's
allocation, the final slice): every operation of the SpMM program that
is not a Mosaic kernel, on the busiest chip."""

from bench import spmm_scopes


def read(r):
    return spmm_scopes.ms_per_call(r, lambda stage, mosaic: not mosaic)
