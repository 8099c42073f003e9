"""Persistent XLA compilation cache for the program's entry points.

Entry points (``repro.launch.serve``, ``repro.launch.train``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`enable` once before
they compile anything.  Importing the package never turns the cache on:
tests and compile rehearsals for a described chip must stay off it (such a
compile is written but cannot be read back without the chip).

The cache directory is part of each entry's key, so it is a fixed path and
never a temporary name: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads it itself), otherwise ``.jax_cache/`` at the checkout root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/runtime/compile_cache.py -> checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
