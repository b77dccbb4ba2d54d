"""Pallas TPU kernel: batched fixed-iteration k-means coreset construction.

This is the paper's clustering-coreset engine (§4.2) re-targeted from a
fixed-function ASIC to a TPU core.  The ASIC insight that transfers directly:
an iteration only needs per-cluster ``(sum, count)`` and the final pass only
``radius`` — so the VMEM working set per window block is

    points (BB, N, D) + centers (BB, K, D) + distance tile (BB, N, K)

with N=64 (60-pt window padded), D≤8, K≤16: a few KB per window, hundreds of
windows per VMEM residency.  The grid is 1-D over window blocks; each program
runs the full Lloyd budget (paper: 4 iterations) so nothing but the coreset
triple ever leaves VMEM — the exact analogue of the paper's "no point storage"
datapath.

MXU note: the (onehot.T @ points) cluster-sum contraction is the one
matmul-shaped op; it runs at HIGHEST precision so the cluster sums keep f32
accuracy on the MXU, as the oracle's do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import default_interpret

__all__ = ["kmeans_coreset_pallas"]


def _kmeans_kernel(points_ref, centers_ref, radii_ref, counts_ref, *,
                   k: int, iters: int):
    pts = points_ref[...].astype(jnp.float32)              # (BB, N, D)
    bb, n, d = pts.shape
    cluster_id = jax.lax.broadcasted_iota(jnp.int32, (bb, n, k), 2)

    # strided init from static slices (Mosaic lowers no 3-D gather)
    centers = jnp.concatenate(
        [pts[:, j:j + 1, :] for j in ((i * n) // k for i in range(k))],
        axis=1)                                             # (BB, K, D)

    def lloyd(_, centers):
        d2 = jnp.sum((pts[:, :, None, :] - centers[:, None, :, :]) ** 2,
                     axis=-1)                               # (BB, N, K)
        assign = jnp.argmin(d2, axis=-1)                    # (BB, N)
        onehot = (assign[..., None] == cluster_id).astype(jnp.float32)
        counts = jnp.sum(onehot, axis=1)                    # (BB, K)
        sums = jnp.einsum("bnk,bnd->bkd", onehot, pts,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        return jnp.where(counts[..., None] > 0,
                         sums / jnp.maximum(counts[..., None], 1.0), centers)

    centers = jax.lax.fori_loop(0, iters, lloyd, centers)

    d2 = jnp.sum((pts[:, :, None, :] - centers[:, None, :, :]) ** 2, axis=-1)
    assign = jnp.argmin(d2, axis=-1)
    onehot = (assign[..., None] == cluster_id).astype(jnp.float32)
    counts = jnp.sum(onehot, axis=1)
    mind2 = jnp.min(d2, axis=-1)                            # (BB, N)
    dist = jnp.sqrt(jnp.maximum(mind2, 0.0))
    radii = jnp.max(onehot * dist[..., None], axis=1)       # (BB, K)

    centers_ref[...] = centers
    radii_ref[...] = radii
    counts_ref[...] = counts.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "iters", "block_b", "interpret"))
def kmeans_coreset_pallas(points: jnp.ndarray, k: int, iters: int = 4,
                          block_b: int = 8, interpret: bool | None = None):
    """Batched clustering-coreset construction.

    Args:
        points: (B, N, D) float window point clouds; B % block_b == 0
            (wrapper in ops.py pads).
        k: clusters (≤16 in the paper's hardware).
        iters: fixed Lloyd budget (paper: 4).
        block_b: windows per program (VMEM tile height).
        interpret: run the kernel body in Python (CPU validation mode);
            ``None`` resolves per backend.

    Returns (centers (B,k,D) f32, radii (B,k) f32, counts (B,k) i32).
    """
    b, n, d = points.shape
    assert b % block_b == 0, f"B={b} not a multiple of block_b={block_b}"
    interpret = default_interpret() if interpret is None else interpret
    grid = (b // block_b,)
    return pl.pallas_call(
        functools.partial(_kmeans_kernel, k=k, iters=iters),
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, n, d), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((block_b, k, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k, d), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        interpret=interpret,
    )(points.astype(jnp.float32))
