"""JAX's persistent compilation cache, placed where the next run finds it.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here sets another directory. Otherwise the cache goes to one
fixed directory inside the checkout (``<repo>/.jax_cache``, git-ignored).
Fixed, because a later run only hits entries it can find at the same
path: a temporary, per-process or timestamped name would never hit.
"""
from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """The directory :func:`enable_compile_cache` uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = compile_cache_dir()
    if path == DEFAULT_DIR:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
