"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory; otherwise
the cache lives at the fixed path ``<repo root>/.jax_cache``.  The path is
part of the cache key, so it never depends on a temporary name, a pid or
the time: a later process on the same checkout finds what an earlier one
compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory and return the path.
    Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
