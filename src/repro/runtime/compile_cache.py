"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``benchmarks.run``) call
:func:`enable_compile_cache` once at start-up; importing this module
changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set in code.  Otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``: the directory is part of the cache's
key, so a path that moved from run to run would never hit.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — this file is ``src/repro/runtime/…``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
