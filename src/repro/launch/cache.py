"""JAX's persistent compilation cache, placed once for every launcher.

A cold launch of a full-width model spends minutes compiling. The cache
keeps each compiled program on disk, keyed by (among other things) its
path, so the path must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
  set here.
* unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Call :func:`enable_compile_cache` before the first compile.
"""
from __future__ import annotations

import os

import jax

#: the fixed default location: the repository checkout's root
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
