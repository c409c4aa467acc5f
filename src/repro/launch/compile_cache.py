"""Where JAX keeps its persistent compilation cache for this checkout.

Every command-line entry point calls ``enable_compile_cache()`` before it
compiles anything. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing here changes. Otherwise the cache goes to the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): a path that does not
move between runs, so a later process of the same checkout finds what an
earlier one compiled. Library code and tests never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
