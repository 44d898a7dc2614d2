"""Persistent XLA compile cache for the entry points.

Called first in each entry point's `main()`, never at import: importing a
module must not change jax's configuration.
"""

from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache. The
# cache key includes the path, so it must not move between runs.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache")


def use_compile_cache() -> str:
    """Keep compiled programs across runs; return the cache directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, jax already reads it, and
    nothing is changed. Otherwise the cache goes to `<checkout>/.jax_cache`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
