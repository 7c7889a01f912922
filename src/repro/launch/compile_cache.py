"""JAX's persistent compilation cache, kept in one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path inside the checkout, never a
temporary or per-process one, so a later process on the same checkout
finds what an earlier one compiled. Nothing here runs at import time;
an entry point calls ``configure_compile_cache`` before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
