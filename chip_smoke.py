"""Smoke run of the Seeker system on a TPU: the main path once, at full width.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the four-chip path only

One chip, in this order, in one process:

1. the Pallas kernels at HAR widths against the ``ref.py`` oracles:
   ``signature_corr_op`` (N=3000 windows x the 12-class bank),
   ``fake_quant_op`` (every HAR weight and activation shape),
   ``kmeans_coreset_op`` and ``importance_select_op``;
2. the HAR classifier trained for 300 SGD steps;
3. the paper's 3-sensor ``seeker_simulate`` against
   ``seeker_simulate_reference`` (decisions and k exactly, bytes and energy
   to tolerance);
4. ``seeker_fleet_simulate`` at N=3000 nodes x S=64 slots with per-node
   streams and labels, run twice (the second call timed), checked against
   its own invariants, and its compiled program checked for the kernel;
5. a few slots of the fleet's payloads served by the host tier
   (``fleet_serve_step`` in queue mode), checked for QoS conservation.

``--chips 4`` runs only the sharded engine and ``fleet_serve_step`` over a
4-device ("data",) mesh, against the single-device engine and a 1-device
mesh: decisions, payload bytes and stored energy bitwise, the psum'd
integer counters exactly.

Any failed check raises, so the exit code is non-zero; off a TPU the script
exits non-zero before any phase.  The windows/s and peak-memory lines are a
smoke reading of one run, not a benchmark.  The last line of standard
output is the JSON result
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_FLEET, S_FLEET = 3000, 64      # fleet phase: nodes x slots
S_SYSTEM = 64                    # 3-sensor system phase: slots
N_KERNEL = 3000                  # kernel phase: windows for the corr engine
B_CORESET = 64                   # kernel phase: windows for the coreset engines
TRAIN_STEPS = 300
HOST_SLOTS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _ready(tree):
    """Block until every device array in ``tree`` is computed."""
    import jax
    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if isinstance(x, jax.Array)]
    return jax.block_until_ready(leaves)


def _close(name: str, got, want, rtol: float, atol: float) -> None:
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    log(f"  {name}: shape {got.shape}, max |kernel - ref| = {err:.3e}")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=name)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_kernels(n_windows: int, b_coreset: int) -> None:
    """Each kernel at HAR widths, compiled, against its jnp oracle."""
    import jax
    import jax.numpy as jnp

    from repro.configs.seeker_har import HAR
    from repro.data.sensors import class_signatures, har_dataset
    from repro.kernels import (fake_quant_op, importance_select_op,
                               kmeans_coreset_op, ref, signature_corr_op)
    from repro.models.har import har_init

    key = jax.random.PRNGKey(0)
    wins, _ = har_dataset(key, n_windows)                   # (N, T, C)
    sigs = class_signatures()                               # (12, T, C)
    _close("signature_corr_op", signature_corr_op(wins, sigs, impl="pallas"),
           ref.signature_corr_ref(wins, sigs), rtol=1e-4, atol=1e-5)

    # fake-quant: round(x / s) may land on the other side of a half-step
    # when the divide differs by an ulp, so agreement is within one step
    tensors = {name: w for name, w in har_init(key, HAR).items()
               if w.ndim >= 2}
    for shape in ((1, HAR.window, HAR.channels),
                  (1, HAR.window // 2, HAR.conv1),
                  (1, HAR.window // 4, HAR.conv2)):
        tensors[f"act{shape}"] = 3.0 * jax.random.normal(key, shape)
    for bits in (16, 12):
        for name, x in tensors.items():
            got = fake_quant_op(x, bits, impl="pallas")
            want = ref.fake_quant_ref(x.reshape(-1, x.shape[-1]), bits
                                      ).reshape(x.shape)
            step = float(jnp.max(jnp.abs(x))) / (2.0 ** (bits - 1) - 1.0)
            off = int(np.sum(np.abs(np.asarray(got) - np.asarray(want))
                             > step / 2))
            log(f"  fake_quant_op {name} {bits}-bit: {off} of {x.size} "
                f"values a step apart")
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=0, atol=step * 1.001,
                                       err_msg=f"fake_quant {name}")

    # the clustering engine's point clouds: (time, value) per channel
    w = wins[:b_coreset]
    tt = jnp.broadcast_to(jnp.linspace(0.0, 4.0, HAR.window)[None, :, None],
                          w.shape)
    pts = jnp.stack([tt, w], axis=-1).transpose(0, 2, 1, 3).reshape(
        -1, HAR.window, 2)                                   # (B*C, T, 2)
    got = kmeans_coreset_op(pts, k=12, impl="pallas")
    want = ref.kmeans_coreset_ref(pts, k=12)
    _close("kmeans_coreset_op centers", got[0], want[0], rtol=1e-5, atol=1e-5)
    _close("kmeans_coreset_op radii", got[1], want[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))

    got = importance_select_op(w, m=20, impl="pallas")
    want = ref.importance_select_ref(w, m=20)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    _close("importance_select_op values", got[1], want[1], rtol=1e-5,
           atol=1e-6)
    _close("importance_select_op weights", got[2], want[2], rtol=1e-4,
           atol=1e-5)


def phase_train(steps: int):
    """The HAR classifier, trained as the benchmarks train it."""
    import jax

    from benchmarks.common import accuracy, train_classifier
    from repro.configs.seeker_har import HAR
    from repro.data.sensors import har_dataset

    params = train_classifier(HAR, har_dataset, steps=steps)
    x, y = har_dataset(jax.random.PRNGKey(2), 512)
    acc = accuracy(params, x, y)
    log(f"  held-out accuracy after {steps} steps: {acc:.4f}")
    check(acc > 0.5, f"training failed: held-out accuracy {acc}")
    return params


def phase_system(params, gen, s: int) -> None:
    """The paper's 3-sensor system against the per-sensor reference loop."""
    import jax

    from repro.configs.seeker_har import HAR
    from repro.core import harvest_trace
    from repro.data.sensors import class_signatures, har_stream
    from repro.serving import seeker_simulate, seeker_simulate_reference

    key = jax.random.PRNGKey(3)
    wins, labels = har_stream(key, s)
    harvest = harvest_trace(key, s, "rf")
    kw = dict(signatures=class_signatures(), qdnn_params=params,
              host_params=params, gen_params=gen, har_cfg=HAR)
    res = seeker_simulate(wins, labels, harvest, **kw)
    ref = seeker_simulate_reference(wins, labels, harvest, **kw)
    for k in ("decisions", "k_trace"):
        np.testing.assert_array_equal(np.asarray(res[k]), np.asarray(ref[k]),
                                      err_msg=k)
    np.testing.assert_allclose(np.asarray(res["payload_bytes"]),
                               np.asarray(ref["payload_bytes"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res["stored_uj"]),
                               np.asarray(ref["stored_uj"]),
                               rtol=1e-5, atol=1e-4)
    log(f"  {s} slots: completed {float(res['completed_frac']):.4f}, "
        f"accuracy when completed {float(res['accuracy_completed']):.4f} "
        f"(reference {float(ref['accuracy_completed']):.4f})")


def fleet_inputs(n: int, s: int, params, gen):
    """Per-node HAR streams, labels and heterogeneous harvest, from seeds."""
    import jax

    from repro.configs.seeker_har import HAR
    from repro.core import fleet_harvest_traces
    from repro.data.sensors import class_signatures, har_stream

    key = jax.random.PRNGKey(1)
    wins, labels = jax.vmap(lambda k: har_stream(k, s))(
        jax.random.split(key, n))                            # (N,S,T,C), (N,S)
    harvest = fleet_harvest_traces(key, n, s)
    kw = dict(signatures=class_signatures(), qdnn_params=params,
              host_params=params, gen_params=gen, har_cfg=HAR, key=key,
              labels=labels.T)
    return wins, harvest, kw


def check_fleet_invariants(res, n: int, s: int) -> None:
    """The engine's own books: histogram, exact wire bytes, completion."""
    from repro.serving import wire_bytes_exact

    hist = np.asarray(res["decision_histogram"])
    alive = int(res["alive_slots"])
    check(int(hist.sum()) == alive,
          f"decision histogram sums to {hist.sum()}, alive slots {alive}")
    check(alive == int(np.asarray(res["alive"]).sum()) == n * s,
          f"alive slots {alive} != {n} x {s} (no churn in this fleet)")
    exact = wire_bytes_exact(res)
    payload = np.rint(np.asarray(res["payload_bytes"])).astype(np.int64)
    recount = int(np.where(np.asarray(res["alive"]), payload, 0).sum())
    check(exact == recount,
          f"wire_bytes_exact {exact} != host recount {recount}")
    approx = float(res["bytes_on_wire"])
    if exact < 2 ** 24:       # float32 holds every integer below 2**24
        check(approx == exact, f"bytes_on_wire {approx} != exact {exact}")
    else:
        check(abs(approx - exact) <= 1e-5 * exact,
              f"bytes_on_wire {approx} far from exact {exact}")
    frac = float(res["completed_frac"])
    check(frac > 0, f"completed_frac {frac}")
    log(f"  histogram {hist.tolist()}, wire bytes {exact}, completed "
        f"{frac:.4f}, fleet accuracy {float(res['fleet_accuracy']):.4f}")


def phase_fleet(params, gen, n: int, s: int):
    """The fleet engine at N x S: compile+run, a timed second run, checks."""
    import jax

    from repro.kernels.ops import default_impl
    from repro.serving import seeker_fleet_simulate

    wins, harvest, kw = fleet_inputs(n, s, params, gen)
    _ready((wins, harvest, kw))
    t0 = time.perf_counter()
    _ready(seeker_fleet_simulate(wins, harvest, **kw))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = seeker_fleet_simulate(wins, harvest, **kw)
    _ready(res)
    second = time.perf_counter() - t0
    check_fleet_invariants(res, n, s)

    labels = kw.pop("labels")
    compiled = jax.jit(functools.partial(
        seeker_fleet_simulate, donate=False, **kw)).lower(
            wins, harvest, labels=labels).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    log(f"  compiled fleet program holds {calls} tpu_custom_call ops")
    if default_impl() == "pallas":
        check(calls > 0, "the fleet program holds no Pallas kernel")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  smoke reading, not a benchmark: first call {first:.3f} s "
        f"(compile included), second call {second:.3f} s = "
        f"{n * s / second:.1f} windows/s; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return wins, res


def host_config(n: int):
    """A host server sized for one payload per node per slot."""
    from repro.configs.seeker_har import HAR
    from repro.host import HostServeConfig

    return HostServeConfig(channels=HAR.channels, k=12, m=20, t=HAR.window,
                           n_classes=HAR.n_classes, n_nodes=n,
                           batch_size=min(256, n), queue_capacity=n,
                           qos_slots=4)


def phase_host(params, gen, wins, res, slots: int) -> None:
    """Serve a few slots of the fleet's payloads through the host tier."""
    import jax
    from jax.sharding import Mesh

    from repro.configs.seeker_har import HAR
    from repro.host import host_server_init, host_server_stats
    from repro.serving import fleet_serve_step

    n = wins.shape[0]
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    cfg = host_config(n)
    state = host_server_init(cfg)
    ingested = 0
    for t in range(slots):
        out = fleet_serve_step(
            wins[:, t], host_params=params, har_cfg=HAR, mesh=mesh,
            key=jax.random.PRNGKey(100 + t), host_state=state,
            serve_cfg=cfg, gen_params=gen, engine_alive=res["alive"][t])
        state = out["host_state"]
        ingested += int(np.asarray(res["alive"][t]).sum())
    stats = host_server_stats(state)
    total = (stats["served"] + stats["deadline_misses"]
             + stats["drops_overflow"] + stats["backlog"])
    log(f"  {slots} slots, {ingested} payloads ingested: served "
        f"{stats['served']}, deadline misses {stats['deadline_misses']}, "
        f"overflow drops {stats['drops_overflow']}, backlog "
        f"{stats['backlog']}, cache hits {stats['cache_hits']}")
    check(total == ingested,
          f"QoS counters hold {total} payloads, {ingested} were ingested")
    check(stats["served"] > 0, "the host tier served nothing")


def phase_mesh(n: int, s: int) -> None:
    """The sharded engine and fleet_serve_step over every device, against
    the single-device engine and a 1-device mesh."""
    import jax
    from jax.sharding import Mesh

    from repro.configs.seeker_har import HAR
    from repro.core.recovery import init_generator
    from repro.host import (host_server_init, host_server_init_stacked,
                            host_server_stats)
    from repro.models.har import har_init
    from repro.serving import (fleet_serve_step, seeker_fleet_simulate,
                               seeker_fleet_simulate_sharded, wire_bytes_exact)

    devices = jax.devices()
    key = jax.random.PRNGKey(0)
    params = har_init(key, HAR)
    gen = init_generator(key, HAR.window, HAR.channels)
    wins, harvest, kw = fleet_inputs(n, s, params, gen)
    mesh = Mesh(np.array(devices), ("data",))

    t0 = time.perf_counter()
    single = seeker_fleet_simulate(wins, harvest, donate=False, **kw)
    _ready(single)
    log(f"  single-device engine: {time.perf_counter() - t0:.3f} s "
        f"(compile included)")
    t0 = time.perf_counter()
    sharded = seeker_fleet_simulate_sharded(wins, harvest, mesh=mesh,
                                            donate=False, **kw)
    _ready(sharded)
    log(f"  sharded engine on {len(devices)} devices: "
        f"{time.perf_counter() - t0:.3f} s (compile included), "
        f"{sharded['padded_nodes']} pad nodes")
    placed = {k: len(sharded[k].sharding.device_set)
              for k in ("decisions", "stored_uj")}
    log(f"  devices holding the sharded traces: {placed}")
    check(all(v == len(devices) for v in placed.values()),
          f"sharded traces are not spread over {len(devices)} devices: "
          f"{placed}")
    for k in ("decisions", "payload_bytes", "stored_uj", "k_trace"):
        np.testing.assert_array_equal(np.asarray(sharded[k]),
                                      np.asarray(single[k]), err_msg=k)
    for k in ("decision_histogram", "completed", "alive_slots",
              "brownout_slots", "brownout_events", "correct",
              "bytes_on_wire_i32"):
        np.testing.assert_array_equal(np.asarray(sharded[k]),
                                      np.asarray(single[k]), err_msg=k)
    check(wire_bytes_exact(sharded) == wire_bytes_exact(single),
          "exact wire bytes differ")
    check_fleet_invariants(sharded, n, s)
    log("  sharded == single-device: decisions, payload bytes, stored "
        "energy, k bitwise; counters exact")

    cfg = host_config(n)
    one = Mesh(np.array(devices[:1]), ("data",))
    slot_kw = dict(host_params=params, har_cfg=HAR, key=key, serve_cfg=cfg,
                   gen_params=gen, engine_alive=single["alive"][0])
    gathered = fleet_serve_step(wins[:, 0], mesh=mesh,
                                host_state=host_server_init(cfg), **slot_kw)
    local = fleet_serve_step(wins[:, 0], mesh=one,
                             host_state=host_server_init(cfg), **slot_kw)
    got, want = (host_server_stats(gathered["host_state"]),
                 host_server_stats(local["host_state"]))
    for k in ("served", "deadline_misses", "drops_overflow", "backlog"):
        check(got[k] == want[k], f"host {k}: mesh {got[k]} vs one {want[k]}")
    check(got["served"] == n, f"served {got['served']} of {n}")
    for f in ("node_id", "valid", "cache_hit"):
        np.testing.assert_array_equal(
            np.asarray(getattr(gathered["slot_output"], f)),
            np.asarray(getattr(local["slot_output"], f)), err_msg=f)
    np.testing.assert_allclose(np.asarray(gathered["slot_output"].logits),
                               np.asarray(local["slot_output"].logits),
                               rtol=1e-4, atol=1e-4)
    log(f"  fleet_serve_step gathered over {len(devices)} devices == 1-device "
        f"mesh: served {got['served']}, node order and cache hits equal")

    per_shard = fleet_serve_step(
        wins[:, 0], mesh=mesh, per_shard_host=True,
        host_state=host_server_init_stacked(cfg, len(devices)), **slot_kw)
    spread = {len(x.sharding.device_set)
              for x in jax.tree_util.tree_leaves(per_shard["host_state"])}
    log(f"  per-shard host servers: qos {per_shard['qos']}, state leaves "
        f"on {spread} devices")
    check(spread == {len(devices)},
          f"per-shard host state is not spread over the mesh: {spread}")
    check(per_shard["qos"]["served"] == n,
          f"per-shard hosts served {per_shard['qos']['served']} of {n}")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path over four chips")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax

    from repro.kernels.ops import default_impl

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache_dir}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
                 f"no phase was run")
    if default_impl() != "pallas":
        sys.exit(f"chip_smoke: kernel dispatch resolved to "
                 f"{default_impl()!r}, not 'pallas'")

    def phase(name, fn, *a):
        log(f"[{name}]")
        t0 = time.perf_counter()
        out = fn(*a)
        log(f"[{name}] ok in {time.perf_counter() - t0:.3f} s")
        return out

    if args.chips == 4:
        if len(devices) != 4:
            sys.exit(f"chip_smoke: --chips 4 needs 4 devices, found "
                     f"{len(devices)}")
        phase("mesh", phase_mesh, N_FLEET, S_FLEET)
    else:
        from repro.configs.seeker_har import HAR
        from repro.core.recovery import init_generator

        gen = init_generator(jax.random.PRNGKey(0), HAR.window, HAR.channels)
        phase("kernels", phase_kernels, N_KERNEL, B_CORESET)
        params = phase("train", phase_train, TRAIN_STEPS)
        phase("system", phase_system, params, gen, S_SYSTEM)
        wins, res = phase("fleet", phase_fleet, params, gen, N_FLEET,
                          S_FLEET)
        phase("host", phase_host, params, gen, wins, res, HOST_SLOTS)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
