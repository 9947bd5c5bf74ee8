"""JAX's persistent compilation cache, switched on by the entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]


def enable_compile_cache(root) -> str:
    """Keep compiled programs across processes; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is named here. Otherwise the cache lives at
    ``<root>/.jax_cache``: a fixed path, because the path is part of what a
    later process must find again. Call once, from an entry point, before
    the first compile (never at import)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # step programs are the expensive compiles, but every program a serving
    # process dispatches is worth keeping
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
