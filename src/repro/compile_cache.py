"""JAX's persistent compilation cache, placed from outside the program.

Every entry point calls :func:`enable_compile_cache` before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here sets another directory.  Otherwise the cache
lives at one fixed path in the checkout, ``<repo>/.jax_cache``
(git-ignored): a cache is found again only at the same path, so the
path is never built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory (``src/repro/`` → repo root).
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
