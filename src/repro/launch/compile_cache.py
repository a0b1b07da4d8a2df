"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point that compiles (``launch`` commands and the swarm
workers they start, ``chip_smoke.py``, the benchmarks) calls
:func:`enable` once before its first compile.  The cache key includes
the directory, so the directory never comes from a temporary name, a pid
or the clock: a cache that moves never hits (DESIGN.md §1).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> Path:
    """``<checkout>/.jax_cache`` (this file is src/repro/launch/)."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache is :func:`default_dir`.

    Either way the key keeps the programs' debug metadata: the ZO step's
    stage names (``obs.stage``) live there, and JAX's default key strips
    it, so a cache written by a program without them would hand back an
    executable whose ops name no stage."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(default_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
