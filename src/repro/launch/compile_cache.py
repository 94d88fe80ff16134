"""JAX's persistent compilation cache, shared by the launchers and
``chip_smoke.py``.

A cold compile of a full-width step takes tens of seconds on a TPU; the
cache makes the second run of the same program skip it.  Call
``enable_compile_cache()`` before the first compile of the process.
"""

from __future__ import annotations

import os
import pathlib

import jax

# fixed, never a temporary name: the directory must be the same on every
# run for the next process to find what this one wrote
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set
    nothing else is set here.  Otherwise the cache lives in ``.jax_cache``
    at the root of the checkout (listed in ``.gitignore``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
