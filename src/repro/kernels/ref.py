"""Pure-jnp oracles for every Pallas kernel in this package.

These are THE definition of correctness: each kernel's test sweeps shapes and
dtypes and asserts allclose against these functions.  They intentionally use
only `jnp` ops (no pallas), in float32, with the exact same algorithmic
choices the kernels make (fixed iteration budgets, strided init, etc.).
Contractions run at ``Precision.HIGHEST`` so the oracles stay float32 on a
TPU too, whose default f32 matmul rounds its inputs to bfloat16.

Kernel inventory (the paper's fixed-function sensor hardware, §4.2, adapted
to VMEM/MXU tiling):

* ``kmeans_coreset_ref``       — batched fixed-iteration Lloyd on windows
* ``importance_select_ref``    — importance weights + top-m selection
* ``signature_corr_ref``       — batched Pearson correlation vs signature bank
* ``fake_quant_ref``           — symmetric uniform quantize-dequantize
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = ["kmeans_coreset_ref", "importance_select_ref", "signature_corr_ref",
           "fake_quant_ref"]


def kmeans_coreset_ref(points: jnp.ndarray, k: int, iters: int = 4):
    """Batched Lloyd with strided init and fixed iteration budget.

    Args:
        points: (B, N, D) float32 point clouds.
        k: clusters.
        iters: fixed Lloyd iterations (paper: 4).

    Returns (centers (B,k,D), radii (B,k), counts (B,k) int32).
    """
    b, n, d = points.shape
    stride_idx = (jnp.arange(k) * n) // k
    centers = points[:, stride_idx, :]                      # (B, k, D)

    def one_iter(centers, _):
        d2 = jnp.sum((points[:, :, None, :] - centers[:, None, :, :]) ** 2, axis=-1)
        assign = jnp.argmin(d2, axis=-1)                    # (B, N)
        onehot = jax.nn.one_hot(assign, k, dtype=points.dtype)  # (B, N, k)
        counts = jnp.sum(onehot, axis=1)                    # (B, k)
        sums = jnp.einsum("bnk,bnd->bkd", onehot, points, precision=_HIGHEST)
        new = jnp.where(counts[..., None] > 0,
                        sums / jnp.maximum(counts[..., None], 1.0), centers)
        return new, None

    centers, _ = jax.lax.scan(one_iter, centers, None, length=iters)
    d2 = jnp.sum((points[:, :, None, :] - centers[:, None, :, :]) ** 2, axis=-1)
    assign = jnp.argmin(d2, axis=-1)
    onehot = jax.nn.one_hot(assign, k, dtype=points.dtype)
    counts = jnp.sum(onehot, axis=1).astype(jnp.int32)
    dist = jnp.sqrt(jnp.take_along_axis(d2, assign[..., None], axis=-1)[..., 0])
    radii = jnp.max(onehot * dist[..., None], axis=1)
    return centers, radii, counts


def _hw_importance(windows: jnp.ndarray, spread: float = 0.25,
                   avg_width: int = 8) -> jnp.ndarray:
    """The *hardware* importance metric: |x - moving_average(x)| summed over
    channels plus a uniform floor.  (The MCU variant of
    ``repro.core.coreset.importance_weights`` — no FFT in fixed-function HW.)

    windows: (B, T, C) -> (B, T) weights summing to 1 per window.
    """
    b, t, c = windows.shape
    kern = jnp.ones((avg_width,), windows.dtype) / avg_width
    pad = avg_width // 2
    xp = jnp.pad(windows, ((0, 0), (pad, avg_width - 1 - pad), (0, 0)), mode="edge")
    # moving average along T for each (b, c)
    ma = jax.vmap(lambda w: jnp.stack(
        [jnp.convolve(w[:, ci], kern, mode="valid", precision=_HIGHEST)
         for ci in range(c)], axis=-1
    ))(xp)
    detr = jnp.abs(windows - ma)
    w = jnp.sum(detr, axis=-1)                               # (B, T)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return (1.0 - spread) * w + spread / t


def importance_select_ref(windows: jnp.ndarray, m: int, spread: float = 0.25):
    """Deterministic top-m importance selection (the fixed-function sampler).

    windows: (B, T, C).  Returns (indices (B,m) int32 ascending,
    values (B,m,C), weights (B,m)).
    """
    w = _hw_importance(windows, spread)
    _, idx = jax.lax.top_k(w, m)
    idx = jnp.sort(idx, axis=-1).astype(jnp.int32)
    vals = jnp.take_along_axis(windows, idx[..., None], axis=1)
    sel_w = jnp.take_along_axis(w, idx, axis=1)
    weights = 1.0 / jnp.maximum(m * sel_w, 1e-9)
    return idx, vals, weights


def signature_corr_ref(windows: jnp.ndarray, signatures: jnp.ndarray) -> jnp.ndarray:
    """Batched per-channel Pearson correlation, averaged over channels.

    windows: (B, T, C); signatures: (L, T, C) -> (B, L).
    """
    wm = windows - jnp.mean(windows, axis=1, keepdims=True)
    sm = signatures - jnp.mean(signatures, axis=1, keepdims=True)
    num = jnp.einsum("btc,ltc->blc", wm, sm, precision=_HIGHEST)
    wn = jnp.sqrt(jnp.sum(wm * wm, axis=1))                 # (B, C)
    sn = jnp.sqrt(jnp.sum(sm * sm, axis=1))                 # (L, C)
    den = wn[:, None, :] * sn[None, :, :]
    return jnp.mean(num / jnp.maximum(den, 1e-9), axis=-1)


def fake_quant_ref(x: jnp.ndarray, bits: int, per_channel: bool = False) -> jnp.ndarray:
    """Symmetric uniform quantize-dequantize at ``bits`` precision.

    Scale = max|x| over the tensor (or per last-dim channel).  This is the
    paper's post-training quantization model for the 16/12-bit edge DNNs.
    """
    if per_channel:
        amax = jnp.max(jnp.abs(x), axis=tuple(range(x.ndim - 1)), keepdims=True)
    else:
        amax = jnp.max(jnp.abs(x))
    qmax = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(amax, 1e-9) / qmax
    return jnp.round(x / scale).clip(-qmax, qmax) * scale
