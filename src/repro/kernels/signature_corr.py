"""Pallas TPU kernel: batched signature-bank Pearson correlation.

The paper's correlation/memoization engine (§3.2.1): every fresh window is
correlated against one stored ground-truth trace per class; corr ≥ 0.95 skips
DNN inference outright.

TPU adaptation: per-channel Pearson correlation of (B, T, C) windows against
an (L, T, C) signature bank is a *fused normalize-then-matmul*: center both
operands along T, compute the (B, L) numerator with a C-batched (T-contracted)
``dot_general`` on the MXU, and divide by the outer product of the L2 norms.
The grid tiles B only: the bank is a handful of classes, so it is one block
that stays resident in VMEM (signature-stationary like the paper's engine),
and the (block_b, L) output block spans the whole bank as Mosaic requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import default_interpret

__all__ = ["signature_corr_pallas"]


def _corr_kernel(win_ref, sig_ref, out_ref):
    w = win_ref[...].astype(jnp.float32)                    # (BB, T, C)
    s = sig_ref[...].astype(jnp.float32)                    # (L, T, C)

    wm = w - jnp.mean(w, axis=1, keepdims=True)
    sm = s - jnp.mean(s, axis=1, keepdims=True)

    # (C, BB, T) x (C, L, T) -> (C, BB, L): channel-batched MXU matmul
    num = jax.lax.dot_general(
        wm.transpose(2, 0, 1), sm.transpose(2, 0, 1),
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                       # (C, BB, L)
    wn = jnp.sqrt(jnp.sum(wm * wm, axis=1))                 # (BB, C)
    sn = jnp.sqrt(jnp.sum(sm * sm, axis=1))                 # (L, C)
    den = wn.T[:, :, None] * sn.T[:, None, :]               # (C, BB, L)
    corr = num / jnp.maximum(den, 1e-9)
    out_ref[...] = jnp.mean(corr, axis=0)                   # (BB, L)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def signature_corr_pallas(windows: jnp.ndarray, signatures: jnp.ndarray,
                          block_b: int = 8,
                          interpret: bool | None = None) -> jnp.ndarray:
    """(B, T, C) x (L, T, C) -> (B, L) mean per-channel Pearson correlations.

    ``block_b`` must divide B and be a multiple of 8 or B itself (the
    wrapper in ops.py pads).  ``interpret=None`` resolves per backend.
    """
    b, t, c = windows.shape
    l, t2, c2 = signatures.shape
    assert (t, c) == (t2, c2)
    assert b % block_b == 0
    interpret = default_interpret() if interpret is None else interpret
    return pl.pallas_call(
        _corr_kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, t, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((l, t, c), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, l), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, l), jnp.float32),
        interpret=interpret,
    )(windows.astype(jnp.float32), signatures.astype(jnp.float32))
