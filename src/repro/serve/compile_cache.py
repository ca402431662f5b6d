"""Persistent JAX compilation cache.

First-compile latency is a cold-start cost: every fresh process pays
seconds to minutes of compilation (sorts at 1e6+ regions compile
slowly) before the first answer, even though the computations are
byte-identical across runs.  ``enable()`` persists compiled executables
to one fixed directory, so a restart is a warm start.  The directory is
part of the cache key in effect, so it never moves between runs:
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/serve/compile_cache.py -> the checkout root
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

_enabled_dir: str | None = None


def cache_dir(requested: str | None = None) -> str:
    """The cache directory in effect: ``$JAX_COMPILATION_CACHE_DIR`` if
    set (and then no other), else ``requested``, else ``DEFAULT_DIR``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR") or requested
            or DEFAULT_DIR)


def enable(requested: str | None = None, *,
           min_compile_time_secs: float = 0.0) -> str:
    """Enable the persistent compilation cache (idempotent).

    ``min_compile_time_secs=0`` caches every executable — query kernels
    compile fast but often, so the default 1 s threshold would skip
    exactly the entries a restart wants.  Returns the directory in
    effect (see ``cache_dir``).
    """
    global _enabled_dir
    path = cache_dir(requested)
    if _enabled_dir == path:
        return path
    Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_time_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled_dir = path
    return path


def enabled_dir() -> str | None:
    """The directory the cache was enabled with (None = not enabled)."""
    return _enabled_dir
