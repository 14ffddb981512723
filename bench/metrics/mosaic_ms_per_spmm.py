"""Device milliseconds per k-vector multiply of the Mosaic kernels under
the SpMM runner's `spmm.kernel` scope, on the busiest chip."""

from bench import spmm_scopes


def read(r):
    return spmm_scopes.ms_per_call(
        r, lambda stage, mosaic: mosaic and stage == "spmm.kernel")
