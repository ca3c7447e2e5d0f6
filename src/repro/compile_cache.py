"""Persistent XLA compile cache for the repo's entry points.

JAX keys its on-disk cache by path, so a directory that moves never
hits.  `enable_compile_cache` keeps the one an operator chose through
``JAX_COMPILATION_CACHE_DIR`` (JAX reads it itself) and otherwise
points the cache at a fixed directory inside the checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile;
    returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
