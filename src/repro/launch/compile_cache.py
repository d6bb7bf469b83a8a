"""JAX's persistent compilation cache, at one fixed place per checkout.

The cache key includes the cache directory, so a directory that moves
between runs never hits.  ``enable()`` is called from the ``main()`` of
each launcher, never at import time.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the cache on and return its directory: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``<checkout>/.jax_cache``."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
