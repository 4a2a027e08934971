"""JAX's persistent compilation cache for the entry points.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself),
and otherwise the cache lives at ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed default: ``.jax_cache`` at the root of the checkout
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on at ``compile_cache_dir()``; returns it.
    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that
    directory and no other is configured here."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
