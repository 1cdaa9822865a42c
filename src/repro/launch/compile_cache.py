"""JAX's persistent compilation cache for the entry points.

Every process that reaches the chip compiles its programs anew unless a
persistent cache holds them.  The cache key includes the cache path, so
the path must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR``
where the environment sets it, else :data:`DEFAULT_DIR`, a fixed
directory inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (``<repo>/.jax_compile_cache``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set
    nothing else is configured here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
