"""Pallas TPU kernels for Seeker's fixed-function sensor hardware (paper
§4.2).  They compile with Mosaic on a TPU (tests/test_tpu_compile.py
compiles each for a described v5e) and are validated in interpret mode
against the pure-jnp oracles in ref.py; off a TPU the ops dispatch to the
oracles.

Kernels:
    kmeans_coreset    — clustering-coreset engine (4-iteration Lloyd)
    importance_select — importance-sampling engine (top-m selection)
    signature_corr    — memoization correlation engine
    fake_quant        — 16/12/8-bit quantized-inference building block
"""
from .ops import (  # noqa: F401
    kmeans_coreset_op, importance_select_op, signature_corr_op, fake_quant_op,
    default_impl, default_interpret,
)
from . import ref  # noqa: F401
