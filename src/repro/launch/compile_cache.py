"""Where the entry points keep JAX's persistent compile cache.

A cold process compiles every step program again; on a TPU that is most of
a short run.  :func:`enable_compile_cache` turns the persistent cache on
for a program entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.bench_stream``).  It is never called on import, and the tests
leave the cache off.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside:
    JAX reads the variable itself and nothing is set here.  Otherwise the
    cache goes to the fixed ``<checkout>/.jax_cache`` (gitignored), so a
    later run in the same checkout finds what an earlier one compiled.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
