"""Where JAX's persistent compilation cache lives.

The cache's directory is part of its key, so it must not move between runs:
a directory chosen per run (a tempdir, a pid, a time) never hits.  Entry
points call :func:`enable_compile_cache` once, before their first compile;
importing the package never touches the setting.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed default, ignored by git: ``<checkout>/.jax_cache``
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
