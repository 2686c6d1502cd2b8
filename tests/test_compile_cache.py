"""Where the entry points keep JAX's persistent compilation cache: in
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else in
``<checkout>/.jax_cache``.  Each case compiles in a fresh interpreter, since
the cache directory is process-wide JAX state."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

from repro.runtime.compile_cache import DEFAULT_DIR

_PROBE = """
from repro.runtime.compile_cache import use_compile_cache
import jax, jax.numpy as jnp
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 3.0 + 7.0)(jnp.arange(5.0)).block_until_ready()
"""


def _probe(**env_over) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    src = str(Path(__file__).resolve().parents[1] / "src")
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=src + os.pathsep + env.get("PYTHONPATH", ""),
               **env_over)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_environment_cache_dir_wins(tmp_path):
    where = tmp_path / "cache"
    assert _probe(JAX_COMPILATION_CACHE_DIR=str(where)) == [str(where)] * 2
    assert any(where.iterdir())


def test_default_cache_dir_is_in_the_checkout():
    assert DEFAULT_DIR == Path(__file__).resolve().parents[1] / ".jax_cache"
    assert _probe() == [str(DEFAULT_DIR)] * 2
    assert any(DEFAULT_DIR.iterdir())
