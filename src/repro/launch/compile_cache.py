"""Persistent JAX compilation cache for the repository's entry points."""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]


def enable_compile_cache(checkout: str) -> str:
    """Keep compiled programs across runs; returns the cache directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
    variable itself, so nothing is set here.  Otherwise the cache is the
    fixed ``<checkout>/.jax_cache`` — never a temporary, per-process or
    dated path, because the directory is part of what a later run must
    find again.  Call from an entry point before its first compile, never
    at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
