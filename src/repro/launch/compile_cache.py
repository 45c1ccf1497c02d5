"""Persistent XLA compile cache for the entry points.

Called by ``chip_smoke.py``, ``examples/serve_lm.py`` and
``benchmarks/run.py`` before their first compile; never when ``repro`` is
imported, and never from tests.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# <repo>/.jax_cache (git-ignored): a fixed path, because the path is part
# of the cache key and a directory that moves never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compile cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``REPO_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
