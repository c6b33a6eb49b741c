"""JAX persistent compilation cache placement for the entry points.

Called by ``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks.run``
(never at import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing here overrides it.  Otherwise the cache goes to one
fixed, git-ignored directory inside the checkout: the directory is part of
the cache's key, so it must not move from run to run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
