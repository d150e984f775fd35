"""JAX's persistent compilation cache, placed from outside the library.

Entry points (``chip_smoke.py``, ``launch/serve``, ``launch/tiled_smoke``,
``benchmarks/run`` and the examples) call :func:`use_compile_cache` once
at start-up; nothing calls it at package import.  A run on a fresh
machine then compiles each kernel once, and a second process on the same
checkout reads the compiled programs back instead of compiling again.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path (it is part of the cache key), never
# a temporary name
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
    directory is left alone; otherwise the cache goes to
    :data:`DEFAULT_DIR`.  The minimum compile time worth caching drops to
    zero, so the one-to-two-second Pallas kernel compiles are kept too.
    """
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
