"""Where JAX keeps its persistent compilation cache.

Entry points (scripts with a ``__main__``) call ``use_compile_cache()``
once, before their first compile; library code and tests never do. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here. Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the cache key includes it, so a directory that moved
between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
