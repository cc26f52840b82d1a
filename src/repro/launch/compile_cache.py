"""Persistent XLA compilation cache for the entry points.

Every entry point (``launch/serve.py``, ``launch/train.py``, the ``easey``
CLI and ``chip_smoke.py``) calls ``enable_compile_cache()`` once, before
its first compile.  Library code and tests never do: a test process that
compiles for a described TPU would write entries it cannot read back.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it at start-up and keeps
  its cache there; nothing is changed here.
* unset: the cache goes to ``<checkout>/.jax_cache``, a fixed path (the
  directory is part of the cache key, so a path that moved would never
  hit) that ``.gitignore`` lists.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
