"""JAX's persistent compilation cache for this repository's entry points.

Every entry point (``chip_smoke.py``, ``python -m repro.launch.train``,
``python -m benchmarks.run``) calls :func:`enable_compile_cache` in its
``main`` before the first compile; importing a module never turns it on.
"""
from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache — a fixed path (the cache keys on it), git-ignored
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` where it is set (and nowhere else), else
    :data:`DEFAULT_DIR` inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
