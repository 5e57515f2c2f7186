"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once, before their first compile.  Nothing here
runs on import: a library module never places the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(checkout: Path) -> str:
    """The cache directory this process compiles into.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and it is
    the only cache used.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    — a fixed path, because the path is part of the cache key: a directory
    that moves between runs never hits."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
