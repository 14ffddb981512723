"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `benchmarks/run.py`) call
`enable_compile_cache()` once before compiling anything; importing the
library never does.  The cache key includes the directory, so the
directory is fixed: `JAX_COMPILATION_CACHE_DIR` when it is set (JAX reads
it itself, so nothing else is set), otherwise `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
