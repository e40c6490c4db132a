"""JAX's persistent compilation cache, placed from outside the process.

Entry points call ``enable()`` first thing (``chip_smoke.py``,
``launch/serve.py``'s ``main``); importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so the cache
  lands there and no other directory is set.
* Otherwise: ``<checkout>/.jax_cache`` (gitignored).  The path is fixed on
  purpose — it is part of what a later run must find again, so it is
  never built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
