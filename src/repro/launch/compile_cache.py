"""Where the entry points keep JAX's persistent compilation cache.

A cold process compiles model0-2's serving steps from scratch; the cache
lets the next process on the same machine skip that. The cache key
includes the directory, so the path must not move between runs: it comes
from ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, and nothing else is set), else it is the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``). Importing the
library never turns the cache on; entry points call
:func:`enable_compile_cache` from their ``main``.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

#: the checkout root: src/repro/launch/compile_cache.py -> parents[3]
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
