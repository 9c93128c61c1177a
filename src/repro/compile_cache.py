"""JAX's persistent compilation cache, at a path placed from outside.

``enable()`` is called by the entry points that compile large programs
(``chip_smoke.py``, ``benchmarks.run``), never at import, so tests and
library users keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, derived from this file: a cache whose directory moves never hits
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set here; otherwise the cache is ``<repo root>/.jax_cache``."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
