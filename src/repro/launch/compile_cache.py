"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, the ``benchmarks/`` scripts and the
``examples/``) call :func:`use_compile_cache` once before their first
compile; the library never does, so importing ``repro`` changes no global
JAX setting.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache lives at a fixed path
inside the checkout: the path is part of the cache key, so a directory
that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
