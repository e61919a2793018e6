"""One home for JAX's persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its
first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here changes. Otherwise the cache lives at a fixed
path inside the checkout, ``<repo>/.jax_cache/`` (gitignored): the path
is part of how a cached program is found again, so a directory made per
run, per process or per day would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
