"""Where JAX keeps compiled programs between runs of the entry points."""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when it is set, else at ``<checkout>/.jax_cache`` (git-ignored). The
    path is fixed because it is part of the cache key: a directory that
    moves never hits. Returns the directory used."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
