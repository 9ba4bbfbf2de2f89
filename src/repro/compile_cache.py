"""JAX's persistent compilation cache, for the entry points that compile
real programs (``chip_smoke.py``, ``benchmarks/run.py``).

The cache directory is part of each entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(jax reads that variable itself, and nothing else is set), otherwise the
fixed ``<repo>/.jax_cache``. Library code never calls this.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
