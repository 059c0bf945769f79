"""Persistent XLA compilation cache placement for the entry points.

Called from each entry point's ``main`` (serve, train, train_retrieval,
the retrieval drill, ``chip_smoke.py``) — never at import time, so
importing a library module leaves JAX's configuration alone.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set here.
* Not set: the cache goes to ``<checkout>/.jax_cache``.  The path is part
  of the cache key, so it is a fixed path, not a temporary one.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
