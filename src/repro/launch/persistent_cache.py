"""JAX's persistent compilation cache, kept in one fixed directory.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`enable_persistent_cache` first
thing; nothing calls it on import.  The directory is part of every cache
entry's identity as far as reuse goes — a cache that moves never hits —
so it is either the one named by ``JAX_COMPILATION_CACHE_DIR`` (which JAX
reads itself; this module then sets nothing) or the fixed ``.jax_cache/``
at the root of the checkout, never a temporary, per-process or timed path.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache — this file lives at <checkout>/src/repro/launch/
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> Path:
    """Where the persistent cache lives for this process."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else DEFAULT_DIR


def enable_persistent_cache() -> Path:
    """Point JAX's persistent compilation cache at :func:`cache_dir`.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and no
    other directory is configured here.  Returns the directory in use.
    """
    import jax
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
