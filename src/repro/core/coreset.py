"""Coreset construction — the heart of Seeker (paper §3.1).

Two construction families, exactly as in the paper:

* **Importance sampling** (cheap, less accurate): magnitude/frequency-driven
  weighted selection of ``m`` sample points from a sensor window.  Unbiased
  under the sampling distribution; ≤7 refinement iterations in the paper's
  hardware — here selection is a single Gumbel-top-k pass (the iterative
  hardware loop is an artifact of the serial MCU datapath, not the math).

* **K-means clustering** (more expensive, more accurate): Lloyd's algorithm
  with a *fixed* iteration budget (paper: converges within 4 iterations) and
  the paper's hardware working-set trick — only per-cluster ``(sum, radius,
  count)`` is kept, never the member points.

Both produce compact, *recoverable* payloads (see :mod:`repro.core.recovery`)
whose byte-accounting reproduces the paper's arithmetic:
raw 60-pt window = 240 B, 12-cluster coreset = 36 B, +4 bit/cluster point
counts = 42 B (5.7x), activity-aware sizing → ≈8.9x (§5.2).

All functions are pure JAX (jit/vmap/scan friendly).  The Pallas-accelerated
versions (the paper's fixed-function coreset engine, C7) live in
``repro.kernels.kmeans_coreset`` / ``repro.kernels.importance_sampling`` and
are validated against these references.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "ClusterCoreset",
    "SamplingCoreset",
    "points_from_window",
    "window_from_points",
    "kmeans_coreset",
    "importance_weights",
    "importance_coreset",
    "topk_importance_coreset",
    "quantize_uniform",
    "dequantize_uniform",
    "encode_cluster_coreset",
    "decode_cluster_coreset",
    "raw_payload_bytes",
    "cluster_payload_bytes",
    "sampling_payload_bytes",
]


class ClusterCoreset(NamedTuple):
    """Clustering coreset: k N-spherical clusters (paper Fig. 4, right).

    ``centers``: (k, D) cluster centers.
    ``radii``:   (k,)  max distance of any member from its center.
    ``counts``:  (k,)  number of member points (the +4-bit recovery parameter,
                 paper §3.2.2 — never observed >16 in the paper or here).
    """

    centers: jnp.ndarray
    radii: jnp.ndarray
    counts: jnp.ndarray


class SamplingCoreset(NamedTuple):
    """Importance-sampling coreset (paper Fig. 4, left).

    ``indices``: (m,) selected time indices (sorted ascending).
    ``values``:  (m, C) selected sample values.
    ``weights``: (m,) inverse-probability weights making sums unbiased.
    ``mean``/``var``: (C,) first/second moments of the *full* window — the
        latent-space conditioning of the paper's recovery GAN (appendix A.1).
    """

    indices: jnp.ndarray
    values: jnp.ndarray
    weights: jnp.ndarray
    mean: jnp.ndarray
    var: jnp.ndarray


# ---------------------------------------------------------------------------
# Window <-> point-cloud plumbing
# ---------------------------------------------------------------------------

def points_from_window(window: jnp.ndarray, time_scale: float | None = None) -> jnp.ndarray:
    """Lift a (T, C) sensor window to a (T, C+1) point cloud.

    Clustering operates on the *geometry* of the signal, so the time axis must
    be a coordinate.  ``time_scale`` makes time commensurate with the value
    range; by default it is the window's peak-to-peak value range (so a
    straight line through time stays "straight" in cluster space).
    """
    if window.ndim == 1:
        window = window[:, None]
    t = window.shape[0]
    if time_scale is None:
        ptp = jnp.max(window) - jnp.min(window)
        time_scale = jnp.maximum(ptp, 1e-6)
    tcoord = jnp.linspace(0.0, 1.0, t, dtype=window.dtype) * time_scale
    return jnp.concatenate([tcoord[:, None], window], axis=-1)


def _pick(mask: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``x`` at the one True of ``mask`` along the last axis, exactly.

    A masked max, not a masked sum, so a picked ``-0.0`` keeps its sign.  The
    compare-and-select fuses into the reduction; the indexing it replaces
    becomes a per-row gather under vmap, which a TPU runs orders of
    magnitude below its memory bandwidth."""
    return jnp.max(jnp.where(mask, x, -jnp.inf), axis=-1)


# one compiled unit, as jnp.interp is: eager callers do not dispatch op by op
@functools.partial(jax.jit, static_argnames="t")
def window_from_points(points: jnp.ndarray, t: int) -> jnp.ndarray:
    """Inverse of :func:`points_from_window`: order the points by their time
    coordinate and resample onto a regular (T, C) grid by linear
    interpolation in time.

    Bitwise the stable ``argsort`` + ``jnp.interp`` of the points, written
    without a sort, a search or a gather: each point's sorted position is its
    stable rank from an (n, n) comparison, each grid point's bracket is a
    count, and the bracket's ends are picked by :func:`_pick`."""
    n = points.shape[0]
    x = lax.index_in_dim(points, 0, axis=1, keepdims=False)
    idx = jnp.arange(n)
    # rank_j = #{i: x_i < x_j} + #{i < j: x_i == x_j}: argsort's stable order
    before = (x[:, None] < x[None, :]) | (
        (x[:, None] == x[None, :]) & (idx[:, None] < idx[None, :]))
    rank = jnp.sum(before, axis=0)
    first, last = rank == 0, rank == n - 1
    x0 = _pick(first, x)
    src = (x - x0) / jnp.maximum(_pick(last, x) - x0, 1e-9)
    grid = jnp.linspace(0.0, 1.0, t)
    # jnp.interp term by term; i is searchsorted(src, grid, side="right")
    i = jnp.clip(jnp.sum(src[None, :] <= grid[:, None], axis=1), 1, n - 1)
    hi = rank[None, :] == i[:, None]                          # (t, n)
    lo = rank[None, :] == i[:, None] - 1
    xp_lo = _pick(lo, src)
    dx = _pick(hi, src) - xp_lo
    delta = grid - xp_lo
    dx0 = jnp.abs(dx) <= np.spacing(np.finfo(src.dtype).eps)
    below = grid < _pick(first, src)
    above = grid > _pick(last, src)
    cols = []
    for c in range(points.shape[1] - 1):
        fp = lax.index_in_dim(points, 1 + c, axis=1, keepdims=False)
        fp_lo = _pick(lo, fp)
        df = _pick(hi, fp) - fp_lo
        f = jnp.where(dx0, fp_lo, fp_lo + (delta / jnp.where(dx0, 1, dx)) * df)
        f = jnp.where(below, _pick(first, fp), f)
        cols.append(jnp.where(above, _pick(last, fp), f))
    return jnp.stack(cols, axis=-1)


def channel_cluster_coresets(window: jnp.ndarray, k: int,
                             iters: int = 4) -> ClusterCoreset:
    """Per-channel 2-D (time, value) clustering coresets — the layout of the
    paper's per-channel FIFO hardware (the 240 B / 36 B / 42 B arithmetic is
    per channel).  Returns a ClusterCoreset with leading channel dim:
    centers (C, k, 2), radii (C, k), counts (C, k)."""
    if window.ndim == 1:
        window = window[:, None]

    def one(col):
        return kmeans_coreset(points_from_window(col[:, None]), k, iters)

    return jax.vmap(one, in_axes=1)(window)


# ---------------------------------------------------------------------------
# K-means clustering coreset (paper §3.1 "Coreset Construction Using
# Clustering"; hardware constraints from §4.2)
# ---------------------------------------------------------------------------

def _init_centers(points: jnp.ndarray, k: int) -> jnp.ndarray:
    """Evenly-strided init — deterministic and cheap, matching the paper's
    fixed-function hardware (no RNG on the sensor)."""
    n = points.shape[0]
    stride_idx = (jnp.arange(k) * n) // k
    return points[stride_idx]


def kmeans_coreset(points: jnp.ndarray, k: int, iters: int = 4) -> ClusterCoreset:
    """Lloyd's k-means with a fixed iteration budget (paper: 4 iterations).

    Only ``(sum, count, radius)`` per cluster survive an iteration — the
    paper's hardware working-set observation (§4.2 item 3) — which is also the
    right VMEM footprint for the Pallas kernel.

    Args:
        points: (N, D) point cloud (use :func:`points_from_window` for
            time-series windows).
        k: number of clusters (paper default 12 for HAR, 15–20 for bearing).
        iters: fixed Lloyd iterations (paper hardware: 4).
    """
    n = points.shape[0]
    centers0 = _init_centers(points, k)

    def lloyd(centers, _):
        d2 = jnp.sum((points[:, None, :] - centers[None, :, :]) ** 2, axis=-1)
        assign = jnp.argmin(d2, axis=1)                       # (N,)
        onehot = jax.nn.one_hot(assign, k, dtype=points.dtype)  # (N, k)
        counts = jnp.sum(onehot, axis=0)                      # (k,)
        sums = onehot.T @ points                              # (k, D)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), centers
        )
        return new_centers, None

    centers, _ = jax.lax.scan(lloyd, centers0, None, length=iters)

    d2 = jnp.sum((points[:, None, :] - centers[None, :, :]) ** 2, axis=-1)
    assign = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(assign, k, dtype=points.dtype)
    counts = jnp.sum(onehot, axis=0).astype(jnp.int32)
    dist = jnp.sqrt(jnp.take_along_axis(d2, assign[:, None], axis=1)[:, 0])
    radii = jnp.max(onehot * dist[:, None], axis=0)
    del n
    return ClusterCoreset(centers=centers, radii=radii, counts=counts)


# ---------------------------------------------------------------------------
# Importance-sampling coreset (paper §3.1 "Coreset Construction Using
# Importance Sampling")
# ---------------------------------------------------------------------------

def importance_weights(window: jnp.ndarray, spread: float = 0.25) -> jnp.ndarray:
    """Importance of each sample = contribution to the frequency response
    (paper: "high enough magnitude in the frequency response") plus a uniform
    floor that guarantees temporal spread.

    Implemented as the magnitude of the mean-detrended signal blended with the
    per-sample spectral energy envelope; a ``spread`` fraction of uniform mass
    keeps far-apart samples selectable (paper: "sampling data which are far
    enough from each other").
    """
    if window.ndim == 1:
        window = window[:, None]
    t = window.shape[0]
    detrended = window - jnp.mean(window, axis=0, keepdims=True)
    mag = jnp.sum(jnp.abs(detrended), axis=-1)
    # spectral envelope: inverse FFT of the top-half spectrum magnitude
    spec = jnp.abs(jnp.fft.rfft(detrended, axis=0))
    # energy each time step contributes to the dominant bands
    envelope = jnp.sum(jnp.abs(jnp.fft.irfft(spec * (spec > jnp.median(spec)), n=t, axis=0)), axis=-1)
    w = mag + envelope
    w = w / jnp.maximum(jnp.sum(w), 1e-9)
    uniform = jnp.full((t,), 1.0 / t, dtype=w.dtype)
    return (1.0 - spread) * w + spread * uniform


def _moments(window: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    if window.ndim == 1:
        window = window[:, None]
    return jnp.mean(window, axis=0), jnp.var(window, axis=0)


def importance_coreset(window: jnp.ndarray, m: int, key: jax.Array,
                       spread: float = 0.25) -> SamplingCoreset:
    """Weighted sampling *without replacement* of ``m`` points via the
    Gumbel-top-k trick (single pass — replaces the MCU's ≤7 serial refinement
    iterations with a parallel selection, same distribution family)."""
    if window.ndim == 1:
        window = window[:, None]
    t = window.shape[0]
    w = importance_weights(window, spread=spread)
    g = -jnp.log(-jnp.log(jax.random.uniform(key, (t,), minval=1e-9, maxval=1.0)))
    scores = jnp.log(jnp.maximum(w, 1e-12)) + g
    _, idx = jax.lax.top_k(scores, m)
    idx = jnp.sort(idx)
    mean, var = _moments(window)
    # Horvitz-Thompson style weights: 1 / (m * p_i) keeps weighted sums unbiased
    weights = 1.0 / jnp.maximum(m * w[idx], 1e-9)
    return SamplingCoreset(indices=idx, values=window[idx], weights=weights,
                           mean=mean, var=var)


def topk_importance_coreset(window: jnp.ndarray, m: int,
                            spread: float = 0.25) -> SamplingCoreset:
    """Deterministic variant (pure top-m by importance) — what the paper's
    fixed-function sampler computes when no RNG is available."""
    if window.ndim == 1:
        window = window[:, None]
    w = importance_weights(window, spread=spread)
    _, idx = jax.lax.top_k(w, m)
    idx = jnp.sort(idx)
    mean, var = _moments(window)
    weights = 1.0 / jnp.maximum(m * w[idx], 1e-9)
    return SamplingCoreset(indices=idx, values=window[idx], weights=weights,
                           mean=mean, var=var)


# ---------------------------------------------------------------------------
# Quantized wire encoding + byte accounting (paper §3.2, §4)
# ---------------------------------------------------------------------------

def quantize_uniform(x: jnp.ndarray, bits: int, lo: jnp.ndarray | float,
                     hi: jnp.ndarray | float) -> jnp.ndarray:
    """Symmetric-range uniform quantization to ``bits`` bits (codes as int32)."""
    levels = (1 << bits) - 1
    xc = jnp.clip(x, lo, hi)
    scale = jnp.maximum(hi - lo, 1e-9)
    return jnp.round((xc - lo) / scale * levels).astype(jnp.int32)


def dequantize_uniform(codes: jnp.ndarray, bits: int, lo: jnp.ndarray | float,
                       hi: jnp.ndarray | float) -> jnp.ndarray:
    levels = (1 << bits) - 1
    scale = jnp.maximum(hi - lo, 1e-9)
    return codes.astype(jnp.float32) / levels * scale + lo


class EncodedClusterCoreset(NamedTuple):
    """The wire format of Table/§3.2: per cluster 2 B center + 1 B radius +
    4 bit count, plus a (lo, hi) range pair shared by the whole payload."""

    center_codes: jnp.ndarray  # (k, D) int32, packed at `center_bits/D` bits per dim
    radius_codes: jnp.ndarray  # (k,)  int32, 8-bit
    counts: jnp.ndarray        # (k,)  int32, 4-bit on the wire
    lo: jnp.ndarray
    hi: jnp.ndarray


def encode_cluster_coreset(cs: ClusterCoreset, center_bits: int = 16,
                           radius_bits: int = 8) -> EncodedClusterCoreset:
    d = cs.centers.shape[-1]
    per_dim_bits = max(center_bits // d, 1)
    lo = jnp.min(cs.centers)
    hi = jnp.max(cs.centers)
    center_codes = quantize_uniform(cs.centers, per_dim_bits, lo, hi)
    rhi = jnp.maximum(jnp.max(cs.radii), 1e-9)
    radius_codes = quantize_uniform(cs.radii, radius_bits, 0.0, rhi)
    return EncodedClusterCoreset(center_codes, radius_codes, cs.counts, lo, rhi * 0 + hi)


def decode_cluster_coreset(enc: EncodedClusterCoreset, center_bits: int = 16,
                           radius_bits: int = 8) -> ClusterCoreset:
    d = enc.center_codes.shape[-1]
    per_dim_bits = max(center_bits // d, 1)
    centers = dequantize_uniform(enc.center_codes, per_dim_bits, enc.lo, enc.hi)
    # radius range was [0, hi-ish]; reuse hi-lo scale conservatively
    rhi = jnp.maximum(enc.hi - enc.lo, 1e-9)
    radii = dequantize_uniform(enc.radius_codes, radius_bits, 0.0, rhi)
    return ClusterCoreset(centers=centers, radii=radii, counts=enc.counts)


def raw_payload_bytes(t: int, bytes_per_value: int = 4) -> int:
    """Paper: 60 fp32 points = 240 B."""
    return t * bytes_per_value


def cluster_payload_bytes(k: int, bytes_center: int = 2, bytes_radius: int = 1,
                          bits_count: int = 4, recoverable: bool = True) -> int:
    """Paper: 12 clusters -> 36 B; +4 bit/cluster counts -> 42 B (§3.2.2)."""
    base = k * (bytes_center + bytes_radius)
    if recoverable:
        base += math.ceil(k * bits_count / 8)
    return base


def sampling_payload_bytes(m: int, bytes_index: int = 1, bytes_value: int = 2,
                           with_moments: bool = True, bytes_moment: int = 2,
                           channels: int = 1) -> int:
    """m selected points: 1 B index + 2 B quantized value per channel;
    +mean/var per channel when the GAN-recovery conditioning is shipped
    (paper A.1)."""
    base = m * (bytes_index + bytes_value * channels)
    if with_moments:
        base += 2 * bytes_moment * channels
    return base
