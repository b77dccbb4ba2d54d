"""Backend dispatch shared by the kernels and their wrappers in ops.py."""
from __future__ import annotations

import jax

__all__ = ["default_interpret", "default_impl"]


def default_interpret() -> bool:
    """Pallas interpret mode: Python-evaluated kernel body off-TPU, the
    compiled Mosaic kernel on a TPU."""
    return jax.default_backend() != "tpu"


def default_impl() -> str:
    """Backend dispatch: the compiled kernel on TPU, the jnp oracle elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"
