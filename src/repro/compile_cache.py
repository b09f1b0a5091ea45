"""JAX's persistent compilation cache for this repository's entry points.

Every entry point (``chip_smoke.py``, ``python -m repro.lab``, the
scripts under ``benchmarks/``) calls :func:`enable_compile_cache` once,
first thing in its ``main``.  Nothing here runs on import: library code
and tests never touch the cache setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
  module sets nothing.
* Unset: the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored).  The path is part of the cache
  key, so it must not vary between runs: no temp directory, pid or
  timestamp.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    Returns the directory in use.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
