"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` once, before
their first compile, so a second run finds the first run's programs.
``import repro`` never calls it, and neither do the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
    cache: JAX reads the variable itself, and nothing else is set.
    Otherwise the cache is ``<checkout>/.jax_cache`` — a fixed path,
    since a directory that moves between runs is never found again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
