"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle,
across shapes and dtypes (the (c) deliverable's kernel validation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (fake_quant_op, importance_select_op,
                           kmeans_coreset_op, signature_corr_op)
from repro.kernels import ref


@pytest.mark.parametrize("b", [1, 7, 8, 24])
# (60, 2): the paper's per-channel (time, value) clouds of a HAR window
@pytest.mark.parametrize("n,d", [(60, 4), (32, 2), (64, 8), (60, 2)])
@pytest.mark.parametrize("k", [4, 12, 16])
def test_kmeans_kernel_matches_ref(b, n, d, k, key):
    pts = jax.random.normal(key, (b, n, d))
    c1, r1, n1 = kmeans_coreset_op(pts, k=k, impl="pallas")
    c2, r2, n2 = ref.kmeans_coreset_ref(pts, k=k)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_kernel_dtypes(dtype, key):
    pts = jax.random.normal(key, (8, 60, 4)).astype(dtype)
    c1, r1, n1 = kmeans_coreset_op(pts, k=12, impl="pallas")
    c2, r2, n2 = ref.kmeans_coreset_ref(pts.astype(jnp.float32), k=12)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,t,c", [(4, 60, 3), (8, 48, 1), (13, 64, 5),
                                   (8, 60, 3)])
@pytest.mark.parametrize("m", [8, 20])
def test_importance_kernel_matches_ref(b, t, c, m, key):
    w = jax.random.normal(key, (b, t, c))
    i1, v1, w1 = importance_select_op(w, m=m, impl="pallas")
    i2, v2, w2 = ref.importance_select_ref(w, m=m)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2),
                               rtol=1e-4, atol=1e-5)


# L=12 is the HAR bank: B=3 is the 3-sensor system (one whole-tile block),
# B=13 pads to two blocks of 8
@pytest.mark.parametrize("b,l", [(4, 5), (16, 12), (9, 3), (3, 12), (13, 12)])
def test_corr_kernel_matches_ref(b, l, key):
    w = jax.random.normal(key, (b, 60, 3))
    s = jax.random.normal(jax.random.fold_in(key, 1), (l, 60, 3))
    c1 = signature_corr_op(w, s, impl="pallas")
    c2 = ref.signature_corr_ref(w, s)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                               rtol=1e-4, atol=1e-5)
    assert bool(jnp.all(jnp.abs(c1) <= 1.0 + 1e-4))


def test_corr_kernel_self_correlation(key):
    w = jax.random.normal(key, (5, 60, 3))
    c = signature_corr_op(w, w, impl="pallas")
    np.testing.assert_allclose(np.asarray(jnp.diag(c)), 1.0, atol=1e-4)


@pytest.mark.parametrize("bits", [8, 12, 16])
@pytest.mark.parametrize("shape", [(33, 70), (4, 60, 3), (256,), (128, 512)])
@pytest.mark.parametrize("per_channel", [False, True])
def test_quant_kernel_matches_ref(bits, shape, per_channel, key):
    x = jax.random.normal(key, shape) * 3
    q1 = fake_quant_op(x, bits, per_channel=per_channel, impl="pallas")
    if per_channel and x.ndim == 1:
        pytest.skip("per-channel needs >=2 dims")
    x2d = x.reshape(-1, shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    q2 = ref.fake_quant_ref(x2d, bits, per_channel=per_channel).reshape(shape)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2),
                               rtol=1e-5, atol=1e-6)


def test_quant_error_bound(key):
    x = jax.random.normal(key, (64, 64))
    for bits in (8, 12, 16):
        q = fake_quant_op(x, bits, impl="pallas")
        scale = float(jnp.max(jnp.abs(x))) / (2 ** (bits - 1) - 1)
        assert float(jnp.max(jnp.abs(q - x))) <= scale / 2 + 1e-6


# ---------------------------------------------------------------------------
# Backend dispatch (ops.py): default impl resolves per backend, and both
# implementations agree wherever the serving path may pick either.
# ---------------------------------------------------------------------------

def test_default_impl_matches_backend():
    from repro.kernels.ops import default_impl
    expect = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert default_impl() == expect


def test_dispatch_impls_agree_on_corr_and_quant(key):
    w = jax.random.normal(key, (6, 60, 3))
    s = jax.random.normal(jax.random.fold_in(key, 1), (12, 60, 3))
    np.testing.assert_allclose(
        np.asarray(signature_corr_op(w, s, impl="ref")),
        np.asarray(signature_corr_op(w, s, impl="pallas")),
        rtol=1e-4, atol=1e-5)
    x = jax.random.normal(key, (4, 60, 3)) * 3
    np.testing.assert_allclose(
        np.asarray(fake_quant_op(x, 12, impl="ref")),
        np.asarray(fake_quant_op(x, 12, impl="pallas")),
        rtol=1e-5, atol=1e-6)


def test_dispatch_ref_is_vmap_and_scan_safe(key):
    """The fleet engine vmaps the quant path and scans the corr path — the
    dispatched default must survive both transforms (interpret-mode Pallas
    historically has not, which is why ref is the off-TPU default)."""
    w = jax.random.normal(key, (5, 60, 3))
    s = jax.random.normal(jax.random.fold_in(key, 1), (4, 60, 3))
    per = jax.vmap(lambda x: fake_quant_op(x[None], 8)[0])(w)
    assert per.shape == w.shape

    def step(carry, win):
        return carry, signature_corr_op(win[None], s)[0]

    _, corr = jax.lax.scan(step, 0, w)
    assert corr.shape == (5, 4)
    np.testing.assert_allclose(np.asarray(corr),
                               np.asarray(signature_corr_op(w, s)),
                               rtol=1e-5, atol=1e-6)
