"""Shared pieces of the benchmark harness: paths, seeds, spans, devices."""
from __future__ import annotations

import contextlib
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    the environment names one, else the fixed ``bench/.jax_cache`` inside
    the checkout.  Every program is cached, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int, stream: int):
    """A PRNG key for one named stream of the run, from a seed of up to 64
    bits (``PRNGKey`` alone keeps only the low 32)."""
    import jax
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


@contextlib.contextmanager
def span(name: str):
    """A host span that lands in the profiler's trace (and costs nothing
    when no trace is being taken)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def block(tree):
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    return jax.block_until_ready([x for x in leaves
                                  if isinstance(x, jax.Array)])
