"""Where JAX's persistent compilation cache lives.

A cold process recompiles every program; the persistent cache lets the
next process on the same machine skip that. Its directory is part of
every entry's lookup, so it must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; the cache
    goes there and nothing here names another directory;
  * unset — the fixed path ``<repo>/.jax_cache`` (gitignored), never a
    temp directory, a PID or a timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    path = os.environ.get(ENV)
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
