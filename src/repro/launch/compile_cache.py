"""JAX's persistent compilation cache, at one fixed place per checkout.

The cache key includes the directory, so a path that moves (a temp dir,
a pid) never hits. Entry points call `enable_compile_cache()` before
their first compile.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> pathlib.Path:
    """Use `JAX_COMPILATION_CACHE_DIR` when it is set (JAX reads it
    itself), else `<checkout>/.jax_cache`. Returns the directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return pathlib.Path(env)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return CHECKOUT_CACHE
