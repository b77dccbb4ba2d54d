"""Where the entry points keep JAX's persistent compilation cache.

A compiled program is keyed partly on the cache directory's path, so the
directory must not move between runs: it is either the one the environment
names through ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself)
or one fixed directory inside the checkout, ``<checkout>/.jax_cache``.
Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/edge_host_serving.py``) call :func:`enable_compile_cache` before
their first compile; the tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (left to JAX
    as it is), else :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
