"""Bytes of the plan's prepared device arrays per nonzero of the matrix
(a count from shapes: padding, indices and gathered layouts included)."""


def read(r):
    if "layout_bytes" not in r.info:
        return None
    return r.info["layout_bytes"] / r.info["nnz"]
