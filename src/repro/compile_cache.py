"""Where compiled programs and tuned plans are kept between runs.

Both caches live at fixed paths inside the checkout, so what a run
reads is built from the repository and earlier runs of it, and a
checkout that moves does not silently miss (the compile cache's key
includes its directory).
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
JAX_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE_DIR))
    return str(JAX_CACHE_DIR)
