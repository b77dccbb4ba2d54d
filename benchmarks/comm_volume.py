"""Distributed comm-volume bench (ours): coreset codecs on the collectives.

(1) Gradient DP all-reduce: dense psum vs Seeker top-k coreset payload —
    wire bytes from the lowered HLO of both train steps on an 8-way DP mesh
    of virtual CPU devices, in a child process held to the CPU
    (``JAX_PLATFORMS=cpu``): it only lowers HLO, so it never competes with
    this process for an accelerator.  A failed child fails the benchmark.
(2) Edge->host activation offload: raw windows vs quantized cluster-coreset
    payload bytes through collective_permute (analytic + codec roundtrip).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.core.compression import (CompressionConfig, compress_activation,
                                    decompress_activation,
                                    wire_bytes_dense_psum,
                                    wire_bytes_kmeans1d,
                                    wire_bytes_topk_allgather)

_SUBPROC = """
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import sharding as shd
from repro.core.compression import CompressionConfig
from repro.data.lm import LMTask, lm_batches
from repro.launch.hlo_analysis import analyze_hlo
from repro.models.config import ModelConfig
from repro.train import (TrainHyper, init_train_state,
                         make_compressed_train_step, make_train_step)

from repro.sharding import make_mesh_compat

mesh = make_mesh_compat((8,), ("data",))
cfg = ModelConfig(name="t", vocab=256, d_model=128, n_layers=4, n_heads=8,
                  n_kv=4, d_ff=512, dtype=jnp.float32)
hyper = TrainHyper()
ccfg = CompressionConfig(topk_ratio=1/64, min_size=1024)
task = LMTask(vocab=256, seq_len=128, batch=16)
batch = lm_batches(task, 0)
with shd.use_sharding(mesh, shd.DP_TP_RULES):
    state = jax.eval_shape(lambda: init_train_state(
        jax.random.PRNGKey(0), cfg, hyper, ccfg))
    sh_state = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), state)
    sh_batch = {"tokens": NamedSharding(mesh, P("data"))}
    dense = make_train_step(cfg, hyper)
    state_d = {k: v for k, v in state.items() if k != "ef"}
    sh_d = {k: v for k, v in sh_state.items() if k != "ef"}
    l_dense = jax.jit(dense, in_shardings=(sh_d, sh_batch)).lower(state_d, batch)
    comp = make_compressed_train_step(cfg, hyper, ccfg, mesh, ("data",))
    l_comp = jax.jit(comp).lower(state, batch)
a = analyze_hlo(l_dense.compile().as_text())
b = analyze_hlo(l_comp.compile().as_text())
print(json.dumps({"dense": a.collective_bytes, "comp": b.collective_bytes,
                  "dense_total": a.total_collective_bytes,
                  "comp_total": b.total_collective_bytes}))
"""


def _grad_rows() -> list[dict]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_SUBPROC)],
                         capture_output=True, text=True, timeout=560, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"gradient-compression HLO child failed "
                           f"(exit {out.returncode}): {out.stderr[-2000:]}")
    d = json.loads(out.stdout.strip().splitlines()[-1])
    return [
        {"name": "comm/grad_dense_psum", "us_per_call": 0.0,
         "wire_bytes_per_dev": d["dense_total"]},
        {"name": "comm/grad_coreset_topk", "us_per_call": 0.0,
         "wire_bytes_per_dev": d["comp_total"],
         "reduction_x": d["dense_total"] / max(d["comp_total"], 1)},
    ]


def run() -> list[dict]:
    rows = _grad_rows()

    # analytic accounting at fleet scale (tinyllama grads over 32-way DP)
    n = 1_100_048_384
    rows.append({"name": "comm/fleet_dense_psum_1.1B_dp32", "us_per_call": 0.0,
                 "wire_bytes_per_dev": wire_bytes_dense_psum(n, 32)})
    rows.append({"name": "comm/fleet_topk64_1.1B_dp32", "us_per_call": 0.0,
                 "wire_bytes_per_dev": wire_bytes_topk_allgather(n, 32, 1 / 64),
                 "reduction_x": wire_bytes_dense_psum(n, 32)
                 / wire_bytes_topk_allgather(n, 32, 1 / 64)})

    # edge->host activation offload codec (paper C1/C2 on the pod axis)
    key = jax.random.PRNGKey(0)
    act = jax.random.normal(key, (64, 60, 3))
    ccfg = CompressionConfig()
    cs = compress_activation(act, ccfg)
    rec = decompress_activation(cs, act.shape)
    err = float(jnp.mean(jnp.abs(rec - act)) / jnp.std(act))
    raw_bytes = act.size * 2   # bf16 wire
    km = wire_bytes_kmeans1d(act.size)
    rows.append({"name": "comm/edge_host_activation_kmeans", "us_per_call": 0.0,
                 "wire_bytes": km, "raw_bytes": raw_bytes,
                 "reduction_x": raw_bytes / km, "rel_err": err})
    return rows
