"""Persistent JAX compilation cache location (one rule for every entry
point that compiles the serving programs).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  configured in code.
* Otherwise: ``<checkout>/.jax_cache`` (gitignored).  The path is fixed —
  never a temp, pid or time-stamped directory — because the cache key
  includes it, so a moving directory would never hit.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache"]

#: the checkout root: src/repro/launch/cache.py -> parents[3]
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return the path in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
