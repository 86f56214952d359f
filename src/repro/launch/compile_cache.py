"""Where the entry points keep JAX's persistent compilation cache.

A compiled program is found again only from the same directory (the path
is part of the cache key), so the directory is fixed: the one named by
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself), and
otherwise ``.jax_cache`` at the root of this checkout. Scripts call
``use_compile_cache()`` under their ``__main__`` guard; importing this
module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
