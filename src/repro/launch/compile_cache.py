"""Persistent XLA compilation cache for the entry points (serve, train,
chip_smoke.py).  Library code and tests never call this."""
from __future__ import annotations

import os
import pathlib

import jax

# fixed location: the cache directory is part of what a later run must
# find again, so it is never derived from a temporary name, pid or time
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across runs.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
    nothing is set here; otherwise the cache lives in ``<repo>/.jax_cache``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
