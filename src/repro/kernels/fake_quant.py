"""Pallas TPU kernel: fused symmetric fake-quantization (16/12/8-bit).

The paper deploys two post-training-quantized DNN copies (16- and 12-bit) on
the sensor's ReRAM crossbars (§4, C6).  On TPU the analogue is fake-quant
(quantize-dequantize) fused into a single VMEM pass: ``round(clip(x/s))*s``
with the scale precomputed per tensor (or per output channel).

The kernel is deliberately trivial compute — its value is *fusion*: one HBM
round-trip instead of the 4 ops XLA would otherwise materialize, and it is
the template every quantized layer in the serving path reuses.  Tiles are
(block_r, block_c): the wrapper pads to 256-row x 512-column tiles, or uses
the whole dim where it is smaller, as Mosaic's block rules allow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import default_interpret

__all__ = ["fake_quant_pallas"]


def _quant_kernel(x_ref, scale_ref, out_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)
    s = scale_ref[...].astype(jnp.float32)                  # (1, 1) or (1, BC)
    q = jnp.round(x / s)
    q = jnp.clip(q, -qmax, qmax)
    out_ref[...] = q * s


@functools.partial(jax.jit, static_argnames=("bits", "per_channel", "block_r",
                                             "block_c", "interpret"))
def fake_quant_pallas(x2d: jnp.ndarray, bits: int, per_channel: bool = False,
                      block_r: int = 256, block_c: int = 512,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Fake-quantize a 2-D tensor (rows, channels). Wrapper pads/reshapes.

    Args:
        x2d: (R, C) float tensor, R % block_r == 0, C % block_c == 0.
        bits: precision (paper: 16 and 12; 8 for the ablation of Fig. 2c).
        per_channel: scale per last-dim channel instead of per tensor.
        interpret: ``None`` resolves per backend.
    """
    r, c = x2d.shape
    interpret = default_interpret() if interpret is None else interpret
    block_r = min(block_r, r)
    block_c = min(block_c, c)
    assert r % block_r == 0 and c % block_c == 0
    qmax = 2.0 ** (bits - 1) - 1.0
    if per_channel:
        amax = jnp.max(jnp.abs(x2d), axis=0, keepdims=True)  # (1, C)
        scale_spec = pl.BlockSpec((1, block_c), lambda i, j: (0, j))
    else:
        amax = jnp.max(jnp.abs(x2d)).reshape(1, 1)
        scale_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    scale = jnp.maximum(amax, 1e-9) / qmax

    grid = (r // block_r, c // block_c)
    return pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            scale_spec,
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=interpret,
    )(x2d.astype(jnp.float32), scale)
