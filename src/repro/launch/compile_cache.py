"""Where JAX keeps its persistent compilation cache.

Call :func:`configure_compile_cache` first thing in an entry point, before
anything compiles.  ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as
is: JAX reads it itself and no other directory is set here.  Otherwise the
cache goes to ``<checkout>/.jax_cache``, a fixed path (never a temporary,
per-process or per-run one), so a later run of the same checkout finds the
programs an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "configure_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root is three levels up.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
