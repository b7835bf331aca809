"""JAX's persistent compilation cache, placed from outside the program.

A cold process on a chip compiles every program it runs, and compiling the
fused matvec and the Krylov loops at paper size takes a large part of a
short run.  The cache key includes the directory, so it has to stay put:

* ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting — it is
  used as it is, and nothing here names another directory;
* otherwise the cache lives at one fixed directory inside the checkout,
  ``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
