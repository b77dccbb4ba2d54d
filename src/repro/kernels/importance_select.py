"""Pallas TPU kernel: importance weighting + top-m selection.

The paper's importance-sampling engine (§4.2): per window, score every sample
by its deviation from a local moving average (the fixed-function stand-in for
"magnitude in the frequency response" — no FFT in a µW datapath), then select
the m most important samples.

TPU adaptation: the MCU engine iterates ≤7 times serially; here one program
holds a (BB, T, C) window block in VMEM, computes the box-filtered deviation
with T-length shifted adds (static slices of the edge-padded block), and runs
an m-step argmax/mask selection loop that marks the picks in a (BB, T) mask.
The mask is already in time order, so the selection comes out *sorted by
time index* (downstream payload encoding is monotone) without a sort: each
pick's slot is its running rank among the picks, a triangular matmul, and
the (BB, m, T) one-hot built from it gathers values and weights on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import default_interpret

__all__ = ["importance_select_pallas"]

_HIGHEST = jax.lax.Precision.HIGHEST


def _moving_average(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Edge-padded box filter along axis 1 of (BB, T, C), static unroll."""
    pad_l = width // 2
    pad_r = width - 1 - pad_l
    first = jnp.repeat(x[:, :1, :], pad_l, axis=1)
    last = jnp.repeat(x[:, -1:, :], pad_r, axis=1)
    xp = jnp.concatenate([first, x, last], axis=1)          # (BB, T+w-1, C)
    t = x.shape[1]
    acc = jnp.zeros_like(x)
    for j in range(width):                                   # static unroll
        acc = acc + xp[:, j:j + t, :]
    return acc / width


def _select_kernel(windows_ref, idx_ref, vals_ref, weights_ref, *,
                   m: int, spread: float, avg_width: int):
    x = windows_ref[...].astype(jnp.float32)                # (BB, T, C)
    bb, t, c = x.shape

    ma = _moving_average(x, avg_width)
    detr = jnp.sum(jnp.abs(x - ma), axis=-1)                # (BB, T)
    w = detr / jnp.maximum(jnp.sum(detr, axis=-1, keepdims=True), 1e-9)
    w = (1.0 - spread) * w + spread / t                     # (BB, T)

    time = jax.lax.broadcasted_iota(jnp.int32, (bb, t), 1)

    def pick(_, carry):
        masked, chosen = carry
        best = jnp.argmax(masked, axis=-1)                  # (BB,)
        hit = time == best[:, None]
        return jnp.where(hit, 0.0, masked), jnp.where(hit, 1.0, chosen)

    _, chosen = jax.lax.fori_loop(0, m, pick, (w, jnp.zeros_like(w)))

    # rank[b, t] = picks strictly before t: a 0/1 triangular matmul (exact)
    before = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
              < jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
              ).astype(jnp.float32)
    rank = jnp.dot(chosen, before, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)      # (BB, T)
    slot = jax.lax.broadcasted_iota(jnp.int32, (bb, m, t), 1).astype(jnp.float32)
    onehot = ((rank[:, None, :] == slot) & (chosen[:, None, :] > 0)
              ).astype(jnp.float32)                         # (BB, m, T)
    times = jax.lax.broadcasted_iota(jnp.int32, (bb, m, t), 2).astype(jnp.float32)
    sel = jnp.sum(onehot * times, axis=-1).astype(jnp.int32)  # ascending
    vals = jnp.einsum("bmt,btc->bmc", onehot, x, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)   # gather via matmul
    sel_w = jnp.sum(onehot * w[:, None, :], axis=-1)        # (BB, m)
    weights = 1.0 / jnp.maximum(m * sel_w, 1e-9)

    idx_ref[...] = sel
    vals_ref[...] = vals
    weights_ref[...] = weights


@functools.partial(jax.jit,
                   static_argnames=("m", "spread", "avg_width", "block_b",
                                    "interpret"))
def importance_select_pallas(windows: jnp.ndarray, m: int, spread: float = 0.25,
                             avg_width: int = 8, block_b: int = 8,
                             interpret: bool | None = None):
    """Deterministic top-m importance selection over a window batch.

    Args:
        windows: (B, T, C) float windows; B % block_b == 0.
        m: samples to keep (paper: 20 for HAR).
        interpret: ``None`` resolves per backend.

    Returns (indices (B,m) i32 ascending, values (B,m,C) f32,
             HT-weights (B,m) f32).
    """
    b, t, c = windows.shape
    assert b % block_b == 0
    interpret = default_interpret() if interpret is None else interpret
    grid = (b // block_b,)
    return pl.pallas_call(
        functools.partial(_select_kernel, m=m, spread=spread,
                          avg_width=avg_width),
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, t, c), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((block_b, m), lambda i: (i, 0)),
            pl.BlockSpec((block_b, m, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, m), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m), jnp.int32),
            jax.ShapeDtypeStruct((b, m, c), jnp.float32),
            jax.ShapeDtypeStruct((b, m), jnp.float32),
        ],
        interpret=interpret,
    )(windows.astype(jnp.float32))
