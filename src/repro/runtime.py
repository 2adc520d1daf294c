"""Process-level setup shared by the entry points (scripts, examples,
benchmark scripts) — never run on library import.

`use_compile_cache()` places JAX's persistent compilation cache. When
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing else
is set here. Otherwise the cache lives at the fixed `<repo>/.jax_cache`:
the directory is part of the cache key, so a path that moved between runs
(a temp dir, a pid or a timestamp) would never hit.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
