"""JAX's persistent compilation cache, placed for the entry points.

Scripts call :func:`enable_compile_cache` once, before their first compile;
library code never does, so importing ``repro`` changes no JAX setting.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

# a fixed path: the cache only hits when a later run looks in the same place
_CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it is left alone; otherwise the cache goes to ``.jax_cache/`` at the
    root of this checkout.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    return str(_CHECKOUT_CACHE_DIR)
