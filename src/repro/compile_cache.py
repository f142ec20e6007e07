"""Where JAX keeps its persistent compilation cache.

Every entry point that drives the chip (``chip_smoke.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and no other path is set here.  Otherwise the cache is ``<repo>/.jax_cache``:
a fixed path, because the path is part of what JAX keys entries on, so a
directory named after a temp file, a pid or the time would never hit.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir, os.pardir))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def cache_dir() -> str:
    """The cache directory: the environment's, else ``<repo>/.jax_cache``."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile, however small or
    fast, and return its directory."""
    import jax
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir()
