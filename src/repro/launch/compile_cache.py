"""Persistent XLA compile cache, set up once at a program's start-up.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing else is configured. Otherwise the cache goes to
``<root>/.jax_cache`` inside the checkout: a fixed path, because the path is
part of what a later run must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax


def enable(root) -> str:
    """Point JAX's persistent compile cache at its one directory; returns it.
    Call before the first compilation, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
