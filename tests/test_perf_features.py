"""Tests for the §Perf structural features: head padding, flash-vjp
attention, sharding prefix fallback, pure-DP rules, elastic re-mesh."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import forward, init_params
from repro.models.config import ModelConfig
from repro.models.flash import flash_banded_attention, flash_causal_attention
from repro.models.layers import dense_attention

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_head_padding_exact_forward_and_grad(key):
    """Zero-padded q-heads + expanded KV == unpadded math exactly."""
    cfg = ModelConfig(name="t", vocab=256, d_model=36, n_layers=2, n_heads=6,
                      n_kv=2, head_dim=8, d_ff=64, dtype=jnp.float32)
    cfg_pad = dataclasses.replace(cfg, head_pad_multiple=8)
    assert cfg_pad.padded_heads == 8
    params = init_params(key, cfg)
    tokens = jax.random.randint(key, (2, 12), 0, 256)
    a = forward(params, cfg, tokens)
    b = forward(params, cfg_pad, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)

    def loss(p, c):
        lg = forward(p, c, tokens)[..., :cfg.vocab]     # exclude vocab pad
        return jnp.mean(lg.astype(jnp.float32) ** 2)

    g1 = jax.grad(lambda p: loss(p, cfg))(params)
    g2 = jax.grad(lambda p: loss(p, cfg_pad))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("softcap", [0.0, 10.0])
def test_flash_causal_matches_dense(softcap, key):
    q = jax.random.normal(key, (2, 64, 2, 3, 16)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 16)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 16))
    ref = dense_attention(q, k, v, causal=True, softcap=softcap)
    out = flash_causal_attention(q, k, v, 16, softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(dense_attention(
        q, k, v, causal=True, softcap=softcap) ** 2), (0, 1, 2))(q, k, v)
    g_out = jax.grad(lambda q, k, v: jnp.sum(flash_causal_attention(
        q, k, v, 16, softcap) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-3, atol=1e-4)


def test_flash_banded_matches_dense_window(key):
    q = jax.random.normal(key, (2, 64, 2, 3, 16)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 16)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 16))
    ref = dense_attention(q, k, v, causal=True, window=24)
    out = flash_banded_attention(q, k, v, 24, 16, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(dense_attention(
        q, k, v, causal=True, window=24) ** 2), (0, 1, 2))(q, k, v)
    g_out = jax.grad(lambda q, k, v: jnp.sum(flash_banded_attention(
        q, k, v, 24, 16, 0.0) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-3, atol=1e-4)


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_spec_for_prefix_fallback():
    """batch=32 on a 512-way ("pod","data","model") rule shards over the
    longest divisible prefix instead of dropping entirely."""
    code = """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import sharding as shd
    from repro.sharding import make_mesh_compat
    mesh = make_mesh_compat((2, 2, 2), ("pod", "data", "model"))
    s = shd.spec_for(("batch",), (8,), mesh, shd.PURE_DP_RULES)
    assert s == P(("pod", "data", "model")), s
    s = shd.spec_for(("batch",), (4,), mesh, shd.PURE_DP_RULES)
    assert s == P(("pod", "data")), s
    s = shd.spec_for(("batch",), (2,), mesh, shd.PURE_DP_RULES)
    assert s in (P("pod"), P(("pod",))), s   # singleton unwraps; jax
    # treats the two spellings as equal
    s = shd.spec_for(("batch",), (3,), mesh, shd.PURE_DP_RULES)
    assert s == P(None), s
    print("OK")
    """
    assert "OK" in _run(code, devices=8)


@pytest.mark.slow
def test_elastic_remesh_restore():
    """A checkpoint written under one mesh restores onto a different mesh
    (elastic scaling), with identical values."""
    code = """
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import save_checkpoint, restore_checkpoint
    from repro.sharding import make_mesh_compat
    mesh8 = make_mesh_compat((8,), ("data",))
    mesh24 = make_mesh_compat((2, 4), ("data", "model"))
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh8, P("data", None)))
    tree = {"w": x}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, tree)
        abstract = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        sh = {"w": NamedSharding(mesh24, P("model", "data"))}
        back = restore_checkpoint(d, 1, abstract, sh)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(x))
    assert back["w"].sharding.mesh.shape == {"data": 2, "model": 4}
    print("OK")
    """
    assert "OK" in _run(code, devices=8)
