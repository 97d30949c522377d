"""Persistent compilation cache for the entry points.

Entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` once at start; library modules never do.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
other directory is set.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of what a later run must find
again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/cache.py``
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
