"""Where JAX keeps its persistent compilation cache for this checkout.

A directory given in ``JAX_COMPILATION_CACHE_DIR`` is left to JAX, which
reads that variable itself.  Otherwise the cache goes to ``.jax_cache`` at
the root of the checkout: a fixed path, because the path is part of the
cache's key, and a directory that moved would never hit.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (call
    before the first compile) and return that directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
