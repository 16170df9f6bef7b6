"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``) call :func:`enable_compile_cache` from ``main()``;
nothing calls it at import, so tests and library users are unaffected.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout: the path is part of the cache key, so
# a directory named after a temp dir, a pid or the time would never hit
IN_TREE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(IN_TREE))
    return str(IN_TREE)
