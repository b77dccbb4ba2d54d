"""Recoverable-coreset tests (paper §3.2.2 + A.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st
from jax._src import core as jax_core

from repro.core import (
    ClusterCoreset, importance_coreset, init_discriminator, init_generator,
    discriminator_apply, kmeans_coreset, points_from_window,
    recover_cluster_points, recover_cluster_window, recover_sampling_window,
    window_from_points,
)
from repro.core.recovery import _uniform_in_ball


def _window(seed, t=60, c=3):
    k = jax.random.PRNGKey(seed)
    tt = jnp.linspace(0, 4 * jnp.pi, t)[:, None]
    return jnp.sin(tt) + 0.1 * jax.random.normal(k, (t, c))


def test_cluster_recovery_2r_property(key):
    """Recovered points lie within each source cluster's ball (the paper's
    2r-approximation: any two points in one cluster are <=2r apart)."""
    pts = points_from_window(_window(0))
    cs = kmeans_coreset(pts, k=8, iters=4)
    rec, mask = recover_cluster_points(cs, key, n_points=60)
    d = jnp.linalg.norm(rec[:, None] - cs.centers[None], axis=-1)
    mind = jnp.min(d, axis=1)
    maxr = jnp.max(cs.radii)
    valid = np.asarray(mask)
    assert bool(jnp.all(mind[valid] <= maxr + 1e-4))


def test_cluster_recovery_count_match(key):
    pts = points_from_window(_window(1))
    cs = kmeans_coreset(pts, k=12, iters=4)
    rec, mask = recover_cluster_points(cs, key, n_points=60)
    assert int(mask.sum()) == int(cs.counts.sum()) == 60


def test_cluster_recovered_window_close(key):
    """Recovered windows approximate the original well enough for inference
    (paper: ~85% accuracy on reconstructions) — check signal-level error."""
    w = _window(2)
    cs = kmeans_coreset(points_from_window(w), k=12, iters=4)
    rec = recover_cluster_window(cs, key, w.shape[0])
    assert rec.shape == w.shape
    err = float(jnp.mean(jnp.abs(rec - w)))
    scale = float(jnp.std(w))
    assert err < 0.75 * scale, (err, scale)


def test_generator_recovery_keeps_transmitted_points(key):
    """A.1: the samples the sensor DID send are written back verbatim."""
    w = _window(3)
    sc = importance_coreset(w, 20, key)
    gen = init_generator(key, w.shape[0], w.shape[1])
    rec = recover_sampling_window(gen, sc, key, w.shape[0])
    assert rec.shape == w.shape
    np.testing.assert_allclose(np.asarray(rec[sc.indices]),
                               np.asarray(sc.values), rtol=1e-5)


def test_generator_discriminator_shapes(key):
    gen = init_generator(key, 60, 3, n_classes=12)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(gen))
    assert n_params < 500_000        # paper: "few hundred thousand parameters"
    disc = init_discriminator(key, 60, 3)
    score = discriminator_apply(disc, _window(4))
    assert score.shape == ()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**30), k=st.integers(4, 16))
def test_recovery_mass_conservation(seed, k):
    key = jax.random.PRNGKey(seed)
    pts = jax.random.normal(key, (48, 3))
    cs = kmeans_coreset(pts, k=k, iters=4)
    rec, mask = recover_cluster_points(cs, key, n_points=48)
    # per-cluster recovered counts match the transmitted counts within the
    # proportional-slot rounding (+-1 per cluster)
    d = jnp.linalg.norm(rec[:, None] - cs.centers[None], axis=-1)
    assign = np.asarray(jnp.argmin(d, axis=1))[np.asarray(mask)]
    rec_counts = np.bincount(assign, minlength=k)
    src_counts = np.asarray(cs.counts)
    # empty clusters stay empty
    assert np.all(rec_counts[src_counts == 0] == 0)
    assert rec_counts.sum() == src_counts.sum()


# ---------------------------------------------------------------------------
# Gather-free cluster recovery: bitwise the sort / searchsorted / interp form
# ---------------------------------------------------------------------------


def _oracle_points(cs, key, n_points):
    k, d = cs.centers.shape
    total = jnp.maximum(jnp.sum(cs.counts), 1)
    cum = jnp.cumsum(cs.counts)
    slot_pos = (jnp.arange(n_points) * total) // n_points
    slot_cluster = jnp.searchsorted(cum, slot_pos, side="right")
    slot_cluster = jnp.clip(slot_cluster, 0, k - 1)
    mask = jnp.arange(n_points) < total
    offs = _uniform_in_ball(key, n_points, d, dtype=cs.centers.dtype)
    pts = cs.centers[slot_cluster] + offs * cs.radii[slot_cluster][:, None]
    return pts, mask


def _oracle_window(points, t):
    order = jnp.argsort(points[:, 0])
    pts = points[order]
    src = (pts[:, 0] - pts[0, 0]) / jnp.maximum(pts[-1, 0] - pts[0, 0], 1e-9)
    grid = jnp.linspace(0.0, 1.0, t)
    cols = [jnp.interp(grid, src, pts[:, 1 + c])
            for c in range(points.shape[1] - 1)]
    return jnp.stack(cols, axis=-1)


def _oracle_recover(cs, key, t):
    if cs.centers.ndim == 3:
        keys = jax.random.split(key, cs.centers.shape[0])

        def one(centers, radii, counts, kk):
            pts, _ = _oracle_points(ClusterCoreset(centers, radii, counts), kk, t)
            return _oracle_window(pts, t)[:, 0]

        return jax.vmap(one)(cs.centers, cs.radii, cs.counts, keys).T
    pts, _ = _oracle_points(cs, key, t)
    return _oracle_window(pts, t)


def _fleet_coresets(case, n=96):
    """(n, C, k, 2) per-channel coresets as the sensor step ships them:
    counts summing to T, the clusters past each node's k_sel zeroed."""
    t, c, k = {"har": (60, 3, 12), "bearing": (120, 1, 18)}.get(case, (60, 3, 12))
    r = np.random.default_rng(7)
    centers = r.normal(size=(n, c, k, 2)).astype(np.float32)
    centers[..., 0] = np.abs(centers[..., 0])
    radii = 0.2 * np.abs(r.normal(size=(n, c, k))).astype(np.float32)
    radii[r.random(radii.shape) < 0.2] = 0.0
    counts = r.multinomial(t, np.full(k, 1.0 / k), size=(n, c)).astype(np.int32)
    if case == "tied":               # radius-0 clusters of several points each
        radii[..., ::2] = 0.0
        counts[...] = 0
        counts[..., ::2] = 2 * t // k
        centers[..., 2::4, 0] = centers[..., ::4, 0]   # shared times, other values
    if case == "zero":
        centers[...] = 0.0
        radii[...] = 0.0
        counts[...] = 0
    if case == "neg_zero":
        centers[:, :, 0, :] = -0.0
        radii[:, :, 0] = 0.0
    if case in ("har", "bearing"):
        k_sel = r.integers(1, k + 1, size=n)
        counts = np.where(np.arange(k)[None, None, :] < k_sel[:, None, None],
                          counts, 0).astype(np.int32)
    return ClusterCoreset(jnp.asarray(centers), jnp.asarray(radii), jnp.asarray(counts)), t


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("case", ["har", "bearing", "tied", "zero", "neg_zero"])
def test_cluster_window_bitwise_oracle(case):
    """The gather-free recovery is the sort / searchsorted / jnp.interp one
    bit for bit: ties, empty coresets and signed zeros included."""
    cs, t = _fleet_coresets(case)
    keys = jax.random.split(jax.random.PRNGKey(11), cs.counts.shape[0])

    def run(fn):
        return jax.jit(jax.vmap(lambda a, b, c, kk: fn(ClusterCoreset(a, b, c), kk, t)))(
            *cs, keys)

    new, ref = run(recover_cluster_window), run(_oracle_recover)
    assert np.array_equal(_bits(new), _bits(ref))


def test_cluster_window_joint_bitwise_oracle():
    """The joint N-D path (centers (k, D), D=4: three value columns)."""
    r = np.random.default_rng(3)
    n, k, d, t = 64, 12, 4, 60
    centers = r.normal(size=(n, k, d)).astype(np.float32)
    centers[:, 1, :] = centers[:, 0, :]                      # a tied pair
    radii = 0.3 * np.abs(r.normal(size=(n, k))).astype(np.float32)
    radii[:, :2] = 0.0
    counts = r.multinomial(t, np.full(k, 1.0 / k), size=n).astype(np.int32)
    cs = ClusterCoreset(jnp.asarray(centers), jnp.asarray(radii), jnp.asarray(counts))
    keys = jax.random.split(jax.random.PRNGKey(5), n)

    def run(fn):
        return jax.jit(jax.vmap(lambda a, b, c, kk: fn(ClusterCoreset(a, b, c), kk, t)))(
            *cs, keys)

    new, ref = run(recover_cluster_window), run(_oracle_recover)
    assert new.shape == (n, t, d - 1)
    assert np.array_equal(_bits(new), _bits(ref))


@pytest.mark.parametrize("n,t", [(60, 60), (48, 60), (60, 33)])
def test_window_from_points_bitwise_oracle(n, t):
    """window_from_points alone, on a window's point cloud with its time
    coordinates shuffled and partly tied, at point counts other than T."""
    r = np.random.default_rng(n + t)
    pts = np.asarray(points_from_window(_window(n, t=n, c=3)))
    pts = pts[r.permutation(n)]
    pts[: n // 4, 0] = pts[n // 4: n // 2, 0]                # tied times
    pts[0, 0] = -0.0
    pts = jnp.asarray(pts)
    new = jax.jit(window_from_points, static_argnums=1)(pts, t)
    ref = jax.jit(_oracle_window, static_argnums=1)(pts, t)
    assert np.array_equal(_bits(new), _bits(ref))


def _primitives(jaxpr):
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax_core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def test_cluster_recovery_has_no_gather_sort_or_scan():
    """Under vmap at HAR shapes the recovery traces to compare-and-select
    only: a per-row gather, sort or search loop runs far below the memory
    roofline on a TPU."""
    cs, t = _fleet_coresets("har", n=4)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    jaxpr = jax.make_jaxpr(jax.vmap(
        lambda a, b, c, kk: recover_cluster_window(ClusterCoreset(a, b, c), kk, t)))(
            *cs, keys)
    used = _primitives(jaxpr.jaxpr)
    assert not used & {"gather", "sort", "scan", "while"}, sorted(used)
