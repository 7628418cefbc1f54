"""JAX's persistent compilation cache for the repo's entry scripts
(``chip_smoke.py``, ``benchmarks/run.py``).

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and no
other directory is configured.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` — a fixed path, because the path is part of the
cache key, so a directory that moves never hits.  Call before the first
compile.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
