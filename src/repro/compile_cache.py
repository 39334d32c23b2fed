"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a directory that moves never
hits. `use_compile_cache()` is called first by the command-line entry
points (`chip_smoke.py`, `python -m repro.sim.run`, `benchmarks/run.py`);
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed directory inside the checkout, listed in .gitignore
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing else is set. Otherwise the cache goes to `DEFAULT_CACHE_DIR`.
    Call it before the first compilation."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
