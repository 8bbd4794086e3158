"""Where JAX keeps its persistent compilation cache for this checkout.

The entry points (``python -m repro.launch.gnn``, ``benchmarks/run.py``,
``chip_smoke.py``) call ``enable_compile_cache`` before their first
compile. A ``JAX_COMPILATION_CACHE_DIR`` from the environment wins and is
left alone: JAX reads it itself. Otherwise the cache goes to
``<checkout>/.jax_cache``, a fixed path (gitignored), so every process run
from the same checkout finds what an earlier one compiled: the directory
is part of the cache key, and a path built from a temporary name, a PID or
the time would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
