"""Where the persistent XLA compile cache lives.

Entry points (``chip_smoke.py``, ``bench.py``, each ``benchmarks/*.py``
``main``, ``serving/worker.py``) call :func:`enable_compile_cache`
before their first compile. ``import paddle_tpu`` and the test suite
never do: six test workers must not share a cache directory.
"""
from __future__ import annotations

import os

from .native_build import REPO_ROOT

# the path is part of every cache key's lookup: it never moves
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when the caller's environment sets it
    (JAX reads that itself; nothing is overridden), otherwise the one
    fixed, git-ignored ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
