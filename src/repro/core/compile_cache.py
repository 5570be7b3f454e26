"""JAX's persistent compilation cache, at one fixed place per checkout.

A simulator scan for a paper-size design takes most of a minute to compile,
so entry points that compile for the device (``chip_smoke.py``, the
``benchmarks/`` scripts) call :func:`use_compile_cache` before their first
JAX computation.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that
directory itself and nothing here overrides it.  Otherwise the cache lives
in ``.jax_cache/`` at the root of the checkout (git-ignored).  The path is
part of each entry's key, so it is never a temporary or per-process name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
