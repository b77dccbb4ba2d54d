"""Public jit'd wrappers around the Pallas kernels, with backend dispatch.

Handles padding to block multiples, dtype coercion and un-padding of the
results.  Call these, not the kernels, from library code.

Dispatch: every op takes ``impl`` — ``"pallas"`` runs the Pallas kernel,
``"ref"`` runs the pure-jnp oracle from :mod:`repro.kernels.ref`.  The
default (``None``) resolves to ``"pallas"`` on a TPU backend, where the
kernels compile with Mosaic, and to ``"ref"`` elsewhere: the oracles are
validated bit-for-tolerance against the kernels (tests/test_kernels.py),
compile to plain XLA on CPU/GPU, and — unlike interpret-mode Pallas — stay
fast under ``vmap``/``scan``, which is what the fleet engine's hot path
needs.  ``interpret`` (default ``None``) likewise resolves per backend:
compiled on a TPU, Python-evaluated elsewhere (how the CPU tests pin the
kernel bodies to the oracles).
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .backend import default_impl, default_interpret
from .fake_quant import fake_quant_pallas
from .importance_select import importance_select_pallas
from .kmeans_coreset import kmeans_coreset_pallas
from .signature_corr import signature_corr_pallas

__all__ = ["kmeans_coreset_op", "importance_select_op", "signature_corr_op",
           "fake_quant_op", "default_interpret", "default_impl"]


def _resolve_impl(impl: str | None) -> str:
    impl = default_impl() if impl is None else impl
    if impl not in ("pallas", "ref"):
        raise ValueError(f"impl must be 'pallas' or 'ref', got {impl!r}")
    return impl


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int) -> tuple[jnp.ndarray, int]:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, mode="edge"), n


def kmeans_coreset_op(points: jnp.ndarray, k: int, iters: int = 4,
                      block_b: int = 8, interpret: bool | None = None,
                      impl: str | None = None):
    """Batched clustering coresets. points: (B, N, D) -> (centers, radii, counts)."""
    if _resolve_impl(impl) == "ref":
        return ref.kmeans_coreset_ref(points.astype(jnp.float32), k=k,
                                      iters=iters)
    padded, b = _pad_axis(points, 0, block_b)
    centers, radii, counts = kmeans_coreset_pallas(
        padded, k=k, iters=iters, block_b=block_b, interpret=interpret)
    return centers[:b], radii[:b], counts[:b]


def importance_select_op(windows: jnp.ndarray, m: int, spread: float = 0.25,
                         avg_width: int = 8, block_b: int = 8,
                         interpret: bool | None = None,
                         impl: str | None = None):
    """Batched top-m importance selection. windows: (B, T, C)."""
    if _resolve_impl(impl) == "ref":
        return ref.importance_select_ref(windows.astype(jnp.float32), m=m,
                                         spread=spread)
    padded, b = _pad_axis(windows, 0, block_b)
    idx, vals, weights = importance_select_pallas(
        padded, m=m, spread=spread, avg_width=avg_width, block_b=block_b,
        interpret=interpret)
    return idx[:b], vals[:b], weights[:b]


def signature_corr_op(windows: jnp.ndarray, signatures: jnp.ndarray,
                      block_b: int = 8, interpret: bool | None = None,
                      impl: str | None = None) -> jnp.ndarray:
    """(B, T, C) vs (L, T, C) -> (B, L) correlations.

    This is the fleet simulator's memoization hot path: every node correlates
    its fresh window against the whole signature bank each slot, so the
    batched form (B = all fleet nodes) is the one that must scale.  The bank
    (L = a handful of classes) is one kernel block; B tiles in blocks of
    ``block_b`` (a multiple of 8).  Under the sharded fleet engine this op
    runs *inside* the shard_map manual region, so B is the local node tile
    (N/d): a tile smaller than ``block_b`` is one whole-tile block instead of
    being padded up.
    """
    if _resolve_impl(impl) == "ref":
        return ref.signature_corr_ref(windows.astype(jnp.float32),
                                      signatures.astype(jnp.float32))
    block_b = min(block_b, windows.shape[0])
    wp, b = _pad_axis(windows, 0, block_b)
    out = signature_corr_pallas(wp, signatures, block_b=block_b,
                                interpret=interpret)
    return out[:b]


def fake_quant_op(x: jnp.ndarray, bits: int, per_channel: bool = False,
                  interpret: bool | None = None,
                  impl: str | None = None) -> jnp.ndarray:
    """Fake-quantize an arbitrary-shape tensor at ``bits`` precision."""
    orig_shape = x.shape
    orig_dtype = x.dtype
    x2d = x.reshape(-1, orig_shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    if _resolve_impl(impl) == "ref":
        out = ref.fake_quant_ref(x2d.astype(jnp.float32), bits=bits,
                                 per_channel=per_channel)
        return out.reshape(orig_shape).astype(orig_dtype)
    r, c = x2d.shape
    block_r = min(256, r)
    block_c = min(512, c)
    # pad with zeros (zeros quantize to zero; amax computed pre-pad inside on
    # padded array is unchanged because |0| adds nothing)
    pr = (-r) % block_r
    pc = (-c) % block_c
    xp = jnp.pad(x2d, ((0, pr), (0, pc)))
    out = fake_quant_pallas(xp, bits=bits, per_channel=per_channel,
                            block_r=block_r, block_c=block_c,
                            interpret=interpret)
    return out[:r, :c].reshape(orig_shape).astype(orig_dtype)
