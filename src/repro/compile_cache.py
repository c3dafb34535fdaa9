"""JAX's persistent compilation cache for the repository's entry points.

Every new plan shape compiles on its first query (seconds on a TPU at
deployment widths), so the entry points that run on a chip turn the
persistent cache on: ``chip_smoke.py``, ``repro.launch.serve_bitwise`` and
``benchmarks/run.py``. Nothing calls this at import time or in tests.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the fixed in-repo cache location (listed in .gitignore); fixed because
#: entries are found again only under the same directory
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here. Otherwise the cache goes to `CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
