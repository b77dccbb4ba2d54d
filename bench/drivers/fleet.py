"""Driver for the fleet engine (``repro.serving.seeker_fleet_simulate``, or
``seeker_fleet_simulate_sharded`` over a ("data",) mesh of the cell's chips
where the configuration asks for more than one).

One long deployment runs as fixed-length segments through the engine's
resume contract (final state, node keys and brown-out flags feed the next
segment), cycling over a horizon of per-node streams made in set-up.  The
window runs segments one after another and counts the node-slots of every
segment whose results were ready before the window closed.  One segment
at a time: the engine's call returns only once its scan has run (on a
v5e, segment j+1's call returned when segment j+1 was done), so a second
segment in flight overlaps nothing and only delays seeing the first.

After the window the sampled nodes' trajectories are replayed by the plain
reference (``bench/reference.py``) and compared (``bench/compare.py``).
"""
from __future__ import annotations

import time

import numpy as np

from bench import common, compare, reference, streams
from bench.common import log, span

TRACE_KEYS = ("decision", "payload", "stored", "k", "logits", "alive")
PICK_KEYS = ("decisions", "payload_bytes", "stored_uj", "k_trace", "logits",
             "alive", "decision_histogram", "alive_slots",
             "bytes_on_wire_i32")


class Fleet:
    """Set-up state of one fleet cell: inputs, weights, program arguments."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp

        from repro.core.energy import BrownoutConfig, EnergyCosts
        from repro.core.recovery import GeneratorParams
        from repro.models.har import HARConfig

        self.cfg, self.traffic, self.devices = cfg, traffic, devices
        self.precision = cfg["matmul_precision"]
        m = cfg["model"]
        self.n = n = cfg["n_nodes"]
        self.seg = traffic["segment_slots"]
        self.horizon = traffic["horizon_slots"]
        if self.horizon % self.seg:
            raise ValueError("horizon_slots must be a multiple of "
                             "segment_slots")
        t, c, n_cls = m["window"], m["channels"], m["n_classes"]

        self.mesh, windows_fn, place = None, _windows, (lambda a: a)
        if len(devices) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            self.mesh = Mesh(np.asarray(devices), ("data",))
            nodes = NamedSharding(self.mesh, PartitionSpec("data"))
            windows_fn = jax.jit(streams.stream_windows,
                                 static_argnames=_WINDOW_STATIC,
                                 out_shardings=nodes)
            place = lambda a: jax.device_put(a, nodes)   # noqa: E731
        self.engine = ("seeker_fleet_simulate_sharded" if self.mesh
                       else "seeker_fleet_simulate")

        self.weights = _init_weights(common.seed_key(seed, 0), m=_frozen(m))
        self.bank = _signatures(cfg["stream"], t, c, n_cls)
        data_key = common.seed_key(seed, 1)
        self.labels = place(_labels(data_key, n, self.horizon, n_cls,
                                    cfg["stream_dwell"]))
        self.harvest = place(_harvest(common.seed_key(seed, 2), n,
                                      self.horizon,
                                      tuple(cfg["harvest_sources"]),
                                      traffic["harvest_scale"]))
        self.fleet_key = common.seed_key(seed, 3)
        self.segments = []
        for j in range(self.horizon // self.seg):
            slots = jnp.arange(j * self.seg, (j + 1) * self.seg)
            wins = windows_fn(cfg["stream"], data_key, self.labels, slots, t,
                              c, n_cls)
            common.block(wins)
            self.segments.append((wins, self.harvest[:, slots],
                                  self.labels[:, slots].T))
        rng = np.random.default_rng(seed)
        self.sample = np.sort(rng.choice(n, size=traffic["sample_nodes"],
                                         replace=False))

        cls, gen = self.weights
        scale = cfg["cost_scale"]
        self.kwargs = dict(
            signatures=self.bank, qdnn_params=cls, host_params=cls,
            gen_params=GeneratorParams(*gen),
            har_cfg=HARConfig(**{k: m[k] for k in (
                "window", "channels", "n_classes", "conv1", "conv2",
                "kernel", "hidden")}),
            costs=EnergyCosts(**{k: v * scale for k, v in
                                 cfg["costs"].items()}),
            k_max=cfg["k_max"], m_samples=cfg["m_samples"],
            quant_bits=cfg["quant_bits"],
            corr_threshold=cfg["corr_threshold"],
            brownout=BrownoutConfig(off_uj=cfg["off_uj"],
                                    restart_uj=cfg["restart_uj"]))
        if self.mesh:
            self.kwargs["mesh"] = self.mesh
        self.idx = jnp.asarray(self.sample)

    def boot(self):
        """The deployment's state at slot 0."""
        import jax.numpy as jnp

        from repro.serving import fleet_node_init, fleet_node_keys
        state = fleet_node_init(self.n, initial_uj=self.cfg["initial_uj"])
        return (state, fleet_node_keys(self.fleet_key, self.n),
                state.stored_uj < jnp.float32(self.cfg["off_uj"]))

    def segment(self, j: int, carry):
        """Dispatch segment ``j`` (cycling over the horizon) from ``carry``;
        returns the sampled picks and the next carry, not yet computed.  The
        engine runs at the configuration's matrix precision."""
        import jax

        import repro.serving
        simulate = getattr(repro.serving, self.engine)
        wins, harv, labels = self.segments[j % len(self.segments)]
        state, keys, browned = carry
        with span("bench.dispatch"), jax.default_matmul_precision(
                self.precision):
            res = simulate(wins, harv, state0=state, node_keys=keys,
                           brownout_state0=browned, labels=labels,
                           **self.kwargs)
        with span("bench.pick"):
            picks = _pick({k: res[k] for k in PICK_KEYS}, self.idx)
        return picks, (res["final_state"], res["final_keys"],
                       res["final_brownout"])


def pick(res: dict, idx) -> dict:
    """The sampled nodes' traces of one segment, and the engine's books:
    histogram total, alive counters, payload recount, exact wire bytes."""
    import jax.numpy as jnp
    tr = {"decision": res["decisions"], "payload": res["payload_bytes"],
          "stored": res["stored_uj"], "k": res["k_trace"],
          "logits": res["logits"], "alive": res["alive"]}
    out = {k: v[:, idx] for k, v in tr.items()}
    pay = jnp.where(res["alive"], jnp.round(res["payload_bytes"]), 0)
    out["check"] = jnp.stack([
        jnp.sum(res["decision_histogram"]), res["alive_slots"],
        jnp.sum(res["alive"].astype(jnp.int32)),
        jnp.sum(pay.astype(jnp.int32))])
    out["wire"] = res["bytes_on_wire_i32"]
    out["histogram"] = res["decision_histogram"]
    return out


def _frozen(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def _jit(fn, static):
    import jax
    return jax.jit(fn, static_argnames=static)


_init_weights = _jit(lambda key, m: reference.init_weights(key, dict(m)),
                     ("m",))
_signatures = _jit(streams.signatures, ("kind", "t", "channels",
                                        "n_classes"))
_labels = _jit(streams.stream_labels, ("n_nodes", "horizon", "n_classes",
                                       "dwell"))
_harvest = _jit(lambda key, n, s, sources, scale: streams.harvest_traces(
    key, n, s, sources) * scale, ("n", "s", "sources", "scale"))
_WINDOW_STATIC = ("kind", "t", "channels", "n_classes")
_pick = _jit(pick, ())
_windows = _jit(streams.stream_windows, _WINDOW_STATIC)


def window(fleet: Fleet, seconds: float, carry):
    """Run segments one after another for ``seconds``, each waited for (its
    picks copied to the host) before the next is dispatched; returns the
    picks of every segment, the time each was ready, and the window
    start."""
    import jax
    picks, ready = [], []
    t0 = time.perf_counter()
    with span("bench.window"):
        j = 0
        while time.perf_counter() - t0 < seconds:
            p, carry = fleet.segment(j, carry)
            with span("bench.block"):
                picks.append(jax.device_get(p))
            ready.append(time.perf_counter())
            j += 1
    return picks, ready, t0


def invariant_failures(picks, n_slots: int, n_nodes: int) -> dict:
    """The engine's own books, per segment: the decision histogram counts
    every alive slot, the alive counter matches the alive trace, and the
    exact wire bytes equal a recount of the payload trace.  Returns the
    faults found, keyed by segment."""
    out = {}
    for j, p in enumerate(picks):
        hist, alive, alive_tr, recount = (int(x) for x in np.asarray(
            p["check"]))
        hi, lo = (int(x) for x in np.asarray(p["wire"]))
        wire = hi * 2 ** 16 + lo
        faults = []
        if not (hist == alive == alive_tr <= n_slots * n_nodes):
            faults.append(f"histogram {hist}, alive slots {alive}, alive "
                          f"trace {alive_tr}")
        if wire != recount:
            faults.append(f"exact wire bytes {wire} != recount {recount}")
        if faults:
            out[j] = faults
    return out


def reference_traces(fleet: Fleet, n_segments: int,
                     precision: str | None = None):
    """The reference's traces of the sampled nodes over the first
    ``n_segments`` segments of the deployment, (S, K[, L]) numpy arrays,
    with matrix products at ``precision`` (by default the configuration's
    stated one)."""
    import jax
    one = fleet.devices[0]
    segs = [(jax.device_put(w[fleet.idx], one),
             jax.device_put(h[fleet.idx], one))
            for w, h, _ in fleet.segments]
    carry = reference.init_carry(fleet.cfg, fleet.fleet_key, fleet.sample)
    out = []
    for j in range(n_segments):
        w, h = segs[j % len(segs)]
        carry, tr = reference.run_segment(fleet.cfg, fleet.weights,
                                          fleet.bank, carry, w, h,
                                          precision or fleet.precision)
        out.append({k: np.asarray(v) for k, v in tr.items()})
    return {k: np.concatenate([o[k] for o in out]) for k in out[0]}


def program_traces(picks) -> dict:
    return {k: np.concatenate([np.asarray(p[k]) for p in picks])
            for k in TRACE_KEYS}


def run(cell: dict, cfg: dict, traffic: dict, limits: dict, seed: int,
        seconds: float, trace: bool, devices, t_start: float) -> dict:
    import jax

    fleet = Fleet(cfg, traffic, seed, devices)
    elapsed = time.perf_counter() - t_start
    log(f"inputs: {fleet.n} nodes x {fleet.horizon}-slot horizon in "
        f"{len(fleet.segments)} segments, ready {elapsed:.3f} s after start;"
        f" set-up peak {common.memory_peak(devices)} B after inputs")

    # warm up: the same call twice, chained through the resume contract
    carry = fleet.boot()
    for j in range(2):
        p, carry = fleet.segment(j, carry)
        common.block(p)
        common.block(carry)
    del p, carry
    carry = fleet.boot()
    common.block(carry)
    compiles = []
    listener = (lambda name, secs, **kw: compiles.append(name)
                if "backend_compile" in name else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; set-up peak {common.memory_peak(devices)} B")

    tdir = None
    if trace:
        import tempfile
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
    picks, ready, t0 = window(fleet, seconds, carry)
    if trace:
        jax.profiler.stop_trace()
    jax.monitoring.clear_event_listeners()
    del carry
    peak = common.memory_peak(devices)

    slots_per_seg = fleet.seg * fleet.n
    in_window = [r for r in ready if r - t0 <= seconds]
    rate = (len(in_window) * slots_per_seg / (in_window[-1] - t0)
            if in_window else 0.0)
    failures = invariant_failures(picks, fleet.seg, fleet.n)
    histogram = np.sum([np.asarray(p["histogram"]) for p in picks], axis=0)
    prog = program_traces(picks)
    del picks
    log(f"window: {len(ready)} segments dispatched, {len(in_window)} ready "
        f"in {seconds} s; {len(compiles)} compiles inside the window; "
        f"peak {peak} B")
    log("segments ready at (s after the window opened): "
        + " ".join(f"{r - t0:.4f}" for r in ready))

    ref = reference_traces(fleet, len(ready))
    numbers = compare.compare(prog, ref)
    correct, rows = compare.judge(numbers, limits)
    correct = correct and not failures
    for j, faults in failures.items():
        log(f"segment {j}: invariant broken: {'; '.join(faults)}")
    log("readings: " + ", ".join(f"{k} {v!r}" for k, v in numbers.items()))

    record = {
        "correct": correct, "attempted": len(ready),
        "failed": len(failures), "checks": rows, "numbers": numbers,
        "end_to_end": {"node_slots_per_s": rate, "setup_s": setup_s,
                       "peak_hbm_gb": peak / 1e9},
        "counts": {"segments": len(ready), "slots": len(ready) * fleet.seg,
                   "node_slots": len(ready) * slots_per_seg,
                   "histogram": histogram.tolist(),
                   "compiles_in_window": len(compiles)},
        "cfg": cfg, "devices": devices,
        "window_s_host": ready[-1] - t0,
    }
    if trace:
        record["trace_dir"] = tdir
    return record
