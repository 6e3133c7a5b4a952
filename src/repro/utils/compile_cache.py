"""JAX's persistent compilation cache for the repo's entry points.

Scripts (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; the library never does,
so importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: the cache is only found again where it was written
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
