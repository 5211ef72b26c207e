"""Where the entry points keep JAX's persistent compilation cache.

Called by the scripts users run (``chip_smoke.py``, ``python -m
repro.serve``, the benchmarks), never when the library is imported.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache goes to one fixed directory inside the
checkout: the path is part of a cache entry's key, so a directory that
moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> Optional[Path]:
    """Point JAX's compile cache at `CACHE_DIR` unless the environment
    already names one; returns the directory set here, or None."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return CACHE_DIR
