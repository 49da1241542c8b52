"""Persistent compilation cache for the repo's entry points.

Each entry point (``launch/serve.py``, ``launch/train.py``,
``launch/dryrun.py``, ``chip_smoke.py``, the scripts) calls
``enable_compile_cache()`` once, before it compiles anything.  Library
modules never touch the cache.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise the fixed ``<repo>/.jax_cache`` (git-ignored).  The path is part
of the cache key, so a fixed path is what lets a later run hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
