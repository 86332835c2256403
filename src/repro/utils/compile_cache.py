"""Where JAX's persistent compilation cache lives for this repository.

A run on a fresh machine compiles every program from scratch; the
persistent cache lets a second run (or a second process) read them back,
which it finds only where the first run wrote them:

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set — the cache goes there and
  nowhere else;
* otherwise ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Nothing is configured when the library is imported: entry points
(``chip_smoke.py``, ``benchmarks/run.py``) call :func:`enable` before their
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the repository root (this file is ``<repo>/src/repro/utils/...``)
REPO_ROOT = Path(__file__).resolve().parents[3]


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_ROOT / ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`, cache
    every program however fast it compiled, and return the directory."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
