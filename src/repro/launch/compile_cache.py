"""JAX's persistent compilation cache, placed from outside.

``enable()`` is called once at start-up by the serving CLI and by
``chip_smoke.py``.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and no directory is set here.  Otherwise the cache lives at
``<checkout>/.jax_cache`` (git-ignored): a fixed path, since the path is
part of what a later run looks the entries up by.  Every compilation is
kept, however short, so a second run with the same cache compiles
nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the default cache directory: the checkout root's ``.jax_cache``
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
