"""JAX's persistent compilation cache for the serving entry points.

Call :func:`enable_compile_cache` from an entry point's ``main`` before the
first compile; never at import, and never from tests.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed path inside the checkout (git-ignored): the directory is part of the
# cache key, so a path that moved between runs would never hit
_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache is ``.jax_cache/`` at the
    root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
