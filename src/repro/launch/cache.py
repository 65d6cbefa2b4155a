"""Where JAX keeps its persistent compilation cache.

A cold start on the chip compiles every program; the persistent cache
lets a second process (or a second run) load them instead.  The cache
directory is part of the cache's key, so it must not move between runs:
a temporary, per-process or timestamped path never hits.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache directory (git-ignored).
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``.jax_cache/`` at the
    root of the checkout.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.normpath(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
