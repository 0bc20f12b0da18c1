"""Persistent compilation cache for the entry points.

A run on a fresh machine compiles every program; JAX's persistent cache
lets the processes of one run, and later runs on the same disk, reuse them.
Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and the cache
goes there, with no other path set in code. Otherwise it goes to a fixed
`.jax_cache/` at the repository root (gitignored): fixed, because the path
is part of what the cache is keyed on.

Only entry points call `enable()` (`chip_smoke.py`, the `repro.launch.*`
mains). Importing the library sets nothing, so tests run without a cache.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV_VAR)
    if not path:
        import jax
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
