"""Persistent compilation cache for entry-point scripts.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`enable_compile_cache` once, before their first compile.
Library code and tests never do: a cache a library turns on behind its
caller's back outlives the call, and tests must compile what they test.

The cache key includes the directory, so the directory is fixed: the
one ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads the
variable itself), else ``<checkout>/.jax_cache``, which git ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path


def cache_entries(path: Path) -> int:
    """Number of entries the cache directory holds (0 before its first
    write)."""
    return sum(1 for _ in path.iterdir()) if path.is_dir() else 0
