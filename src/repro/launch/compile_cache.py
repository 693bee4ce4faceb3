"""Persistent XLA compilation cache for the entry points.

Called by every CLI / benchmark entry point after its pre-jax flags are
set, never at library import.  ``JAX_COMPILATION_CACHE_DIR``, when set,
is where JAX keeps the cache and no other directory is set here;
otherwise the cache lives at the fixed in-checkout path ``.jax_cache``
(listed in ``.gitignore``), or in ``.jax_cache`` of the working directory
when the package runs from an install rather than a checkout.  The path is part of the cache key's
reach — a directory that moves between runs never hits — so it is never
built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[3]


def default_dir() -> Path:
    """``<checkout>/.jax_cache`` when this module runs from a checkout's
    ``src/``, else ``.jax_cache`` of the working directory."""
    if (_CHECKOUT / "src" / "repro" / "launch").is_dir():
        return _CHECKOUT / ".jax_cache"
    return Path.cwd() / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(default_dir())
        jax.config.update("jax_compilation_cache_dir", path)
    # every program of a fresh process is worth keeping, however quick
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
