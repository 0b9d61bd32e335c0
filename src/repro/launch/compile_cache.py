"""Where JAX keeps its persistent compilation cache.

Every entry point (`chip_smoke.py`, `benchmarks/run.py`, the examples) calls
`use_compile_cache()` before its first compile.  A cache set from outside
wins: when `JAX_COMPILATION_CACHE_DIR` is in the environment, JAX reads it
itself and nothing here sets another directory.  Otherwise the cache lives
at `<checkout>/.jax_cache`, a fixed path (the path is part of the cache
key, so a directory that moves would never hit).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
