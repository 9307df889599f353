"""JAX's persistent compilation cache for the repository's entry points.

Every command-line entry point (``chip_smoke.py``, ``repro.launch.*``,
``benchmarks/*.py``) calls :func:`enable_compile_cache` before it compiles
anything, so a second run on the same machine loads its programs instead of
compiling them again. Library code and tests never call it.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

#: checkout root: src/repro/utils/compile_cache.py -> three levels up from src
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads it
    itself and no other directory is configured. Otherwise the cache lives at
    the fixed path ``<checkout>/.jax_cache`` — fixed because the path is part
    of the cache key, so a directory derived from a temp dir, a pid or a time
    would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
