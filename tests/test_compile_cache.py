"""Where the entry points keep JAX's persistent compilation cache
(:mod:`repro.compile_cache`).  Each case runs in a fresh CPU process, so
this test process's own JAX config is never touched."""
import json
import os
import pathlib
import subprocess
import sys

from repro.compile_cache import CHECKOUT_CACHE_DIR

ROOT = pathlib.Path(__file__).resolve().parents[1]

_SNIPPET = """
import json, sys
import jax
from repro.compile_cache import enable_compile_cache
d = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 2.0 + 1.0)(jax.numpy.ones(4)).block_until_ready()
print(json.dumps({"dir": d, "config": jax.config.jax_compilation_cache_dir}))
"""


def _run(mode: str, **env_extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    out = subprocess.run([sys.executable, "-c", _SNIPPET, mode], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_environment_dir_is_left_to_jax_and_receives_the_cache(tmp_path):
    cache = tmp_path / "jax-cache"
    got = _run("compile", JAX_COMPILATION_CACHE_DIR=str(cache))
    assert got == {"dir": str(cache), "config": str(cache)}
    assert any(cache.iterdir()), "the compile wrote nothing to the cache"


def test_default_dir_is_fixed_inside_the_checkout_and_ignored():
    got = _run("resolve")
    assert got == {"dir": str(CHECKOUT_CACHE_DIR),
                   "config": str(CHECKOUT_CACHE_DIR)}
    assert CHECKOUT_CACHE_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert f"{CHECKOUT_CACHE_DIR.name}/" in ignored
