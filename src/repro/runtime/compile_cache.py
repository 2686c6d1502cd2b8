"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``python -m repro.dse``) call ``use_compile_cache``
once before they compile anything.  A ``JAX_COMPILATION_CACHE_DIR`` from the
environment wins: JAX reads it itself and nothing is set here.  Otherwise the
cache goes to ``<checkout>/.jax_cache``, resolved from this file's location,
so every run from the same checkout finds the entries of the runs before it
(the directory is part of the cache key: a path that moves never hits).
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
