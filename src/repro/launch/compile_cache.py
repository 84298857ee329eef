"""Where JAX keeps its persistent compilation cache.

``$JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it
itself, so no other directory is set in code.  Otherwise the cache sits
at one fixed path inside the checkout (``<root>/.jax_cache``, listed in
``.gitignore``) — fixed because the path is part of the cache's key,
so a per-run directory would never hit.
"""
from __future__ import annotations

import os

import jax


def enable_compile_cache(root: str) -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
