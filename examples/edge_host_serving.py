"""The paper's system end-to-end: an EH-WSN of 3 body sensors + host.

    PYTHONPATH=src python examples/edge_host_serving.py [--source rf]
    PYTHONPATH=src python examples/edge_host_serving.py --fleet 64
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python examples/edge_host_serving.py --fleet 64 --sharded
    PYTHONPATH=src python examples/edge_host_serving.py --fleet 64 \
        --churn 0.3 --chunk 32
    PYTHONPATH=src python examples/edge_host_serving.py --fleet 64 \
        --intermittent
    PYTHONPATH=src python examples/edge_host_serving.py --fleet 24 \
        --host-queue
    PYTHONPATH=src python examples/edge_host_serving.py --fleet 64 \
        --emit-metrics metrics.json --trace-out trace.json

Trains the HAR classifier, builds the memoization signature bank, then
streams activity windows through the full Seeker decision flow under a
harvested-energy trace, reporting the Fig.11/12-style metrics: completion
fraction, accuracy, decision mix, and communication volume vs raw.

``--fleet N`` instead simulates N independent nodes with heterogeneous
harvest modalities in one batched scan (the fleet engine), reporting
per-modality completion and fleet-level wire volume.  ``--churn FRAC``
makes the fleet intermittent (duty-cycled per-node alive traces: nodes
brown out, freeze, rejoin); ``--chunk SLOTS`` streams the window stream in
segments through the resume contract instead of one long scan.
``--intermittent`` scales the harvest down to scarcity, turns on the
supercap brown-out hysteresis, and runs the staged intermittent-inference
lane (docs/ENERGY_MODEL.md): DEFER slots become staged progress that
suspends across brown-outs and emits D7 early exits / D8 full-depth
results slots later.

``--host-queue`` streams a *churny* fleet trace — nodes dropping in and out
slot to slot, periodically re-transmitting identical payloads — through the
host-tier serving subsystem (``repro.host``: QoS-deadline ring queue, EDF
fixed-shape microbatch scheduler, signature-keyed recovery cache) and
prints deadline-miss and cache-hit rates plus the compile-shape count.

``--emit-metrics FILE`` turns on the ``repro.obs`` telemetry lanes for the
fleet/host run and writes the metric summary JSON (with the host tier:
queue-sojourn and end-to-end QoS percentiles); ``--trace-out FILE`` records
wall-clock spans as Chrome-trace/Perfetto JSON (docs/OBSERVABILITY.md).
"""
import argparse
import collections
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.seeker_har import HAR
from repro.core import (D6_PARTIAL, DEFER, EH_SOURCES, fleet_harvest_traces,
                        fleet_source_assignment, harvest_trace)
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_dataset, har_stream
from repro.models.har import har_apply, har_init
from repro.serving import (seeker_fleet_simulate,
                           seeker_fleet_simulate_sharded, seeker_simulate)
from repro.sharding import make_mesh_compat


def train_classifier(key):
    params = har_init(key, HAR)
    xs, ys = har_dataset(jax.random.fold_in(key, 1), 1024)

    def loss_fn(p, x, y):
        logp = jax.nn.log_softmax(har_apply(p, x))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    @jax.jit
    def step(p, x, y):
        _, g = jax.value_and_grad(loss_fn)(p, x, y)
        return jax.tree_util.tree_map(lambda a, b: a - 3e-2 * b, p, g)

    for i in range(300):
        idx = jax.random.randint(jax.random.fold_in(key, 100 + i), (64,),
                                 0, xs.shape[0])
        params = step(params, xs[idx], ys[idx])
    return params


def fleet_demo(key, params, gen, wins, labels, n_nodes: int,
               sharded: bool = False, churn: float = 0.0, chunk: int = 0,
               intermittent: bool = False, emit_metrics: str | None = None):
    """N heterogeneous nodes in one batched scan: the fleet engine.

    ``sharded`` splits the node axis over every visible device (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get a CPU
    mesh) — same traces, fleet aggregates psum-ed across shards.
    ``churn`` > 0 runs the churny fleet: each node follows a
    duty-cycled alive trace (duty = 1 - churn) and browns out/rejoins
    mid-deployment.  ``chunk`` > 0 streams the windows in chunk-slot
    segments instead of one long scan (bitwise-identical results).
    ``intermittent`` scales the harvest down to scarcity, enables the
    supercap brown-out hysteresis, and runs the staged inference lane
    (D6 suspend / D7 early exit / D8 full depth).
    """
    import time

    from repro.core import (BrownoutConfig, IntermittentConfig,
                            fleet_alive_traces)
    from repro.models.har import har_aux_init
    from repro.serving import seeker_fleet_simulate_streamed

    s = wins.shape[0]
    harvest = fleet_harvest_traces(key, n_nodes, s)
    alive = None
    if churn > 0:
        alive = fleet_alive_traces(key, n_nodes, s, duty=1.0 - churn)
    kw = dict(signatures=class_signatures(), qdnn_params=params,
              host_params=params, gen_params=gen, har_cfg=HAR,
              labels=labels, alive=alive)
    if intermittent:
        harvest = harvest * 0.15          # scarcity: make DEFER the norm
        kw.update(brownout=BrownoutConfig(),
                  intermittent=IntermittentConfig(),
                  aux_params=har_aux_init(jax.random.fold_in(key, 7), HAR))
    if sharded:
        kw["mesh"] = make_mesh_compat((jax.device_count(),), ("data",))
    if emit_metrics:
        kw["telemetry"] = True
    t0 = time.time()
    if chunk > 0:
        res = seeker_fleet_simulate_streamed(wins, harvest, chunk=chunk,
                                             **kw)
    elif sharded:
        res = seeker_fleet_simulate_sharded(wins, harvest, **kw)
    else:
        res = seeker_fleet_simulate(wins, harvest, **kw)
    jax.block_until_ready(res["decisions"])
    dt = time.time() - t0

    decisions = np.asarray(res["decisions"])              # (S, N)
    # a D6 suspension put nothing on the wire yet: not completed
    completed = (decisions != DEFER) & (decisions != D6_PARTIAL)
    correct = (np.asarray(res["preds"]) == np.asarray(labels)[:, None]) \
        & completed & (decisions <= 5)
    print(f"\nfleet of {n_nodes} nodes x {s} slots in {dt:.2f}s "
          f"({n_nodes * s / dt:.0f} windows/sec incl. compile)")
    if chunk > 0:
        print(f"streamed in {res['n_chunks']} chunks of {chunk} slots "
              f"(peak window memory {min(chunk, s) / s:.2f}x one long scan)")
    if alive is not None:
        up = int(res["alive_slots"])
        print(f"churn: nodes up {100 * up / (n_nodes * s):.0f}% of slots "
              f"(duty {1 - churn:.2f}); dead slots DEFER with frozen state "
              f"and rejoin in place")
    if sharded:
        print(f"node axis sharded over {jax.device_count()} devices "
              f"(mesh axes {res['node_axes']}, {res['padded_nodes']} inert "
              f"pad nodes)")
    print(f"decision histogram {np.asarray(res['decision_histogram']).tolist()}"
          f" (alive slots only), fleet accuracy "
          f"{100 * float(res['fleet_accuracy']):.1f}%, completed "
          f"{100 * float(res['completed_frac']):.1f}%")
    if intermittent:
        it_final = res["final_intermittent"]
        print(f"intermittent lane (scarce harvest x0.15, brown-out "
              f"hysteresis on): {int(res['it_full'])} staged full-depth "
              f"(D8), {int(res['it_early'])} early exits (D7), "
              f"{int(np.asarray(it_final.active).sum())} inferences still "
              f"suspended in the carry at end of run; "
              f"{int(res['brownout_slots'])} browned-out slots survived "
              f"with progress frozen in place")
    print("per-modality stats (nodes cycle rf/wifi/piezo/solar):")
    node_src = fleet_source_assignment(n_nodes)
    ladder_comp = completed & (decisions <= 5)
    suffix = " (ladder path)" if intermittent else ""
    for si, src in enumerate(EH_SOURCES):
        sel = node_src == si
        if sel.any():
            n_comp = ladder_comp[:, sel].sum()
            acc = correct[:, sel].sum() / max(n_comp, 1)
            print(f"  {src:6s} {100 * completed[:, sel].mean():5.1f}% "
                  f"completed, {100 * acc:5.1f}% accurate when "
                  f"completed{suffix}")
    wire = float(res["bytes_on_wire"])
    raw = completed.sum() * float(res["raw_bytes_per_window"])
    print(f"bytes on wire: {wire:.0f} vs {raw:.0f} raw-equivalent "
          f"({raw / max(wire, 1e-9):.1f}x reduction)")
    if emit_metrics:
        from repro.obs import metrics_summary
        summary = metrics_summary(res["telemetry_spec"], res["telemetry"])
        with open(emit_metrics, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"wrote telemetry lanes to {emit_metrics}")


def host_queue_demo(key, params, gen, wins, n_nodes: int, args,
                    emit_metrics: str | None = None):
    """Churny fleet -> host-tier serving subsystem (queue/EDF/cache).

    Each node follows an on/off duty cycle (intermittent power) and, while
    on, offloads one coreset payload per slot; a node re-transmits the same
    window for a few consecutive slots (periodic activities), so the host's
    signature cache sees D0-style repetition.  Every 4th node ships a D4
    sampling payload (GAN recovery path); the rest ship D3 cluster coresets.
    """
    import time

    import jax.numpy as jnp

    from repro.core.coreset import channel_cluster_coresets, importance_coreset
    from repro.host import (HostServeConfig, cluster_entries, host_ensemble,
                            host_serve_slot, host_server_init,
                            host_server_stats, sampling_entries,
                            serve_trace_count)
    from repro.serving import encode_wire_coresets, encode_wire_samples

    slots, pool = args.windows, min(args.windows, 32)
    cfg = HostServeConfig(
        channels=HAR.channels, k=12, m=20, t=HAR.window,
        n_classes=HAR.n_classes, n_nodes=n_nodes,
        batch_size=args.host_batch, queue_capacity=4 * n_nodes,
        cache_capacity=4 * pool, qos_slots=args.qos,
        telemetry=bool(emit_metrics))

    # pre-encode both payload kinds for the window pool (the edge side)
    centers, radii, counts = jax.vmap(
        lambda w: channel_cluster_coresets(w, k=cfg.k, iters=4))(wins[:pool])
    c_pool = cluster_entries(encode_wire_coresets(centers, radii, counts),
                             cfg.m)
    sc = jax.vmap(lambda w, k_: importance_coreset(w, cfg.m, k_))(
        wins[:pool], jax.random.split(key, pool))
    s_pool = sampling_entries(
        encode_wire_samples(sc.indices, sc.values, sc.mean, sc.var), cfg.k)

    rng = np.random.RandomState(0)
    duty = rng.uniform(0.3, 0.9, size=n_nodes)        # per-node duty cycle
    phase = rng.randint(0, 8, size=n_nodes)
    node_ids = jnp.arange(n_nodes, dtype=jnp.int32)
    is_sampling = node_ids % 4 == 3                   # D4 senders
    state = host_server_init(cfg)
    kw = dict(cfg=cfg, host_params=params, gen_params=gen, base_key=key)

    t0 = time.time()
    ingested = 0
    for s in range(slots):
        # churn: a node is up when its duty-cycled phase says so
        active = (rng.rand(n_nodes) < duty) \
            & (((s + phase) // 4) % 2 == 0)
        # repetition: a node re-sends the same window for 4 slots
        widx = jnp.asarray((node_ids * 7 + (s // 4)) % pool)
        entries = jax.tree_util.tree_map(
            lambda c, sp: jnp.where(
                jnp.reshape(is_sampling, (-1,) + (1,) * (c.ndim - 1)),
                sp[widx], c[widx]),
            c_pool, s_pool)
        ingested += int(active.sum())
        state, _ = host_serve_slot(state, entries, node_ids,
                                   jnp.asarray(active), **kw)
    # drain the backlog with empty ingest slots
    none = jnp.zeros((n_nodes,), bool)
    empty = jax.tree_util.tree_map(lambda a: a[widx], c_pool)
    while host_server_stats(state)["backlog"] > 0:
        state, _ = host_serve_slot(state, empty, node_ids, none, **kw)
    dt = time.time() - t0

    stats = host_server_stats(state, cfg)
    ens = host_ensemble(state)
    print(f"\nhost queue: {n_nodes} churny nodes x {slots} slots "
          f"({ingested} payloads) in {dt:.2f}s "
          f"({ingested / dt:.0f} payloads/sec incl. compile)")
    print(f"  served {stats['served']}, deadline misses "
          f"{stats['deadline_misses']}, overflow drops "
          f"{stats['drops_overflow']} -> deadline-miss rate "
          f"{100 * stats['deadline_miss_rate']:.1f}%, QoS-fail rate "
          f"{100 * stats['qos_fail_rate']:.1f}% "
          f"(bound {cfg.qos_slots} slots, batch {cfg.batch_size})")
    print(f"  cache: {stats['cache_hits']} hits / {stats['cache_misses']} "
          f"misses -> hit rate {100 * stats['cache_hit_rate']:.1f}% "
          f"(bitwise-identical to recomputation)")
    print(f"  compiled serve shapes: {serve_trace_count(cfg)} "
          f"(fixed-shape EDF microbatches; churn never re-traces)")
    answered = np.asarray(ens["counts"]) > 0
    agree = (np.asarray(ens["pred_mean"]) == np.asarray(ens["pred_vote"]))
    agree_pct = 100 * float(agree[answered].mean()) if answered.any() else 0.0
    print(f"  per-node ensemble: {int(answered.sum())}/{n_nodes} nodes "
          f"answered (mean-logit vs majority-vote agreement "
          f"{agree_pct:.0f}% over answered nodes)")
    if emit_metrics:
        print(f"  queue sojourn p50/p95/p99: {stats['sojourn_p50']:.2f}/"
              f"{stats['sojourn_p95']:.2f}/{stats['sojourn_p99']:.2f} slots; "
              f"end-to-end p99 {stats['e2e_p99']:.2f} slots")
        with open(emit_metrics, "w") as f:
            json.dump(stats["telemetry"], f, indent=1)
        print(f"  wrote telemetry lanes to {emit_metrics}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--source", default="rf",
                    choices=["rf", "wifi", "piezo", "solar"])
    ap.add_argument("--windows", type=int, default=128)
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="simulate N heterogeneous nodes with the fleet "
                         "engine instead of the 3-sensor ensemble")
    ap.add_argument("--sharded", action="store_true",
                    help="with --fleet: shard the node axis over every "
                         "visible device (CPU: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--churn", type=float, default=0.0, metavar="FRAC",
                    help="with --fleet: intermittent fleet — each node "
                         "follows a duty-cycled alive trace with duty "
                         "1-FRAC, browning out and rejoining mid-run")
    ap.add_argument("--intermittent", action="store_true",
                    help="with --fleet: scarce harvest + brown-out "
                         "hysteresis + the staged intermittent-inference "
                         "lane — DEFER slots advance a staged quantized "
                         "DNN that suspends across brown-outs and emits "
                         "D7 early exits / D8 full-depth results "
                         "(docs/ENERGY_MODEL.md)")
    ap.add_argument("--chunk", type=int, default=0, metavar="SLOTS",
                    help="with --fleet: stream windows in SLOTS-slot "
                         "segments through the resume contract instead of "
                         "one long scan (bitwise-identical)")
    ap.add_argument("--host-queue", action="store_true",
                    help="stream a churny fleet trace through the host-tier "
                         "serving subsystem (QoS queue + EDF scheduler + "
                         "recovery cache) and report deadline-miss / "
                         "cache-hit rates")
    ap.add_argument("--host-batch", type=int, default=8,
                    help="host EDF microbatch size (--host-queue)")
    ap.add_argument("--qos", type=int, default=3,
                    help="QoS deadline in slots after arrival (--host-queue)")
    ap.add_argument("--emit-metrics", default=None, metavar="FILE",
                    help="run with telemetry lanes on and write the "
                         "metric summary JSON (fleet or host-queue modes)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="record the run as Chrome-trace/Perfetto JSON")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.obs import trace as obs_trace
    if args.trace_out:
        obs_trace.enable()

    key = jax.random.PRNGKey(0)
    print("training HAR classifier on synthetic MHEALTH ...")
    with obs_trace.span("example.train", cat="example"):
        params = train_classifier(key)
    gen = init_generator(key, HAR.window, HAR.channels)
    wins, labels = har_stream(key, args.windows)

    try:
        if args.host_queue:
            with obs_trace.span("example.host_queue", cat="example"):
                host_queue_demo(key, params, gen, wins, args.fleet or 16,
                                args, emit_metrics=args.emit_metrics)
            return

        if args.fleet:
            with obs_trace.span("example.fleet", cat="example"):
                fleet_demo(key, params, gen, wins, labels, args.fleet,
                           sharded=args.sharded, churn=args.churn,
                           chunk=args.chunk,
                           intermittent=args.intermittent,
                           emit_metrics=args.emit_metrics)
            return

        with obs_trace.span("example.single_node", cat="example"):
            _single_node_demo(key, params, gen, wins, labels, args)
    finally:
        if args.trace_out:
            obs_trace.export_chrome_trace(args.trace_out)
            print(f"wrote {args.trace_out} (load at ui.perfetto.dev)")


def _single_node_demo(key, params, gen, wins, labels, args):
    harvest = harvest_trace(key, args.windows, args.source)

    print(f"running Seeker over {args.windows} windows on '{args.source}' "
          f"harvest (mean {float(harvest.mean()):.1f} uJ/slot) ...")
    res = seeker_simulate(wins, labels, harvest,
                          signatures=class_signatures(), qdnn_params=params,
                          host_params=params, gen_params=gen, har_cfg=HAR)

    dec = collections.Counter(np.asarray(res["decisions"]).tolist())
    # NB code 5 is DEFER (sense only); Table 2's D5_RAW is a cost ROW,
    # not a reachable decision — see docs/ENERGY_MODEL.md
    names = {0: "D0 memo", 1: "D1 fullDNN", 2: "D2 qDNN", 3: "D3 cluster",
             4: "D4 sampling", 5: "DEFER", 6: "D6 suspend",
             7: "D7 earlyexit", 8: "D8 stagedfull"}
    print("\ndecision mix:")
    for d, n in sorted(dec.items()):
        print(f"  {names[d]:12s} {n:4d}  ({100*n/args.windows:.1f}%)")
    sent = np.asarray(res["decisions"]) != 5
    payload = float(np.mean(np.asarray(res["payload_bytes"])[sent])) if sent.any() else 0
    raw = float(res["raw_bytes"][0]) * HAR.channels
    print(f"\ncompleted:          {float(res['completed_frac'])*100:.1f}%")
    print(f"accuracy(completed): {float(res['accuracy_completed'])*100:.1f}%")
    print(f"mean payload:       {payload:.1f} B vs raw {raw:.0f} B "
          f"({raw/max(payload,1e-9):.1f}x reduction)")


if __name__ == "__main__":
    main()

