"""Compile the Pallas kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib, so each kernel is lowered and
compiled here for one chip of a ``v5e:2x2`` topology at the widths the
system runs: what Mosaic refuses (block shapes, gathers, sorts) fails here
and not on the chip.  Nothing runs.  The topology is described inside a
module-scoped fixture, never while a module is imported, so every pytest
worker collects the same tests and only the worker given this file loads
the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.seeker_har import HAR
from repro.kernels import (fake_quant_op, importance_select_op,
                           kmeans_coreset_op, signature_corr_op)

L = HAR.n_classes                     # signature bank: one trace per class


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# N=3: the paper's 3-sensor system; 8: one block; 750: a shard tile of the
# N=3000 fleet on 4 chips; 3000: the single-chip fleet
@pytest.mark.parametrize("n", [3, 8, 750, 3000])
def test_signature_corr_compiles(one_chip, n):
    text = _compile_text(
        lambda w, s: signature_corr_op(w, s, impl="pallas", interpret=False),
        one_chip, (n, HAR.window, HAR.channels), (L, HAR.window, HAR.channels))
    assert "tpu_custom_call" in text


# HAR weights (conv1, conv2, dense, head) and the activations the quantized
# DNN fake-quantizes (window, pooled conv1, pooled conv2)
@pytest.mark.parametrize("shape", [
    (HAR.kernel, HAR.channels, HAR.conv1),
    (HAR.kernel, HAR.conv1, HAR.conv2),
    ((HAR.window // 4) * HAR.conv2, HAR.hidden),
    (HAR.hidden, HAR.n_classes),
    (1, HAR.window, HAR.channels),
    (1, HAR.window // 2, HAR.conv1),
    (1, HAR.window // 4, HAR.conv2),
])
@pytest.mark.parametrize("bits", [16, 12])
def test_fake_quant_compiles(one_chip, shape, bits):
    text = _compile_text(
        lambda x: fake_quant_op(x, bits, impl="pallas", interpret=False),
        one_chip, shape)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(8, 64, 3), (8, HAR.window, 2)])
def test_kmeans_coreset_compiles(one_chip, shape):
    text = _compile_text(
        lambda p: kmeans_coreset_op(p, k=12, impl="pallas", interpret=False),
        one_chip, shape)
    assert "tpu_custom_call" in text


def test_importance_select_compiles(one_chip):
    text = _compile_text(
        lambda w: importance_select_op(w, m=20, impl="pallas",
                                       interpret=False),
        one_chip, (8, HAR.window, HAR.channels))
    assert "tpu_custom_call" in text
