"""JAX's persistent compilation cache for this repository's entry points.

Every entry point (``chip_smoke.py``, ``python -m repro.lab``, the
scripts under ``benchmarks/``) calls :func:`enable_compile_cache` once,
first thing in its ``main``.  Nothing here runs on import: library code
and tests never touch the cache setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
  module sets no path.
* Unset: the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored).  The path is part of the cache
  key, so it must not vary between runs: no temp directory, pid or
  timestamp.

Either way the key includes each program's metadata
(``jax_compilation_cache_include_metadata_in_key``).  JAX's default
strips it, so a program that differs from a cached one only in its
named scopes or source lines would load the other's executable, and a
profile would show the other program's op names: the fused loop's
``dial.*`` scopes, which the benchmark charges device time to, would
be missing or stale.
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

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
