"""Driver for the host tier (``repro.host.host_serve_slot``) serving the D3
and D4 payloads of a fleet.

Set-up runs the configuration's fleet through the fleet engine
(``seeker_fleet_simulate`` with its coreset output) for ``horizon_slots``
slots and turns each slot into the host's ingest lane
(``repro.host.fleet_slot_entries``: one row a node, masked to the alive D3
and D4 nodes), kept on the device.  The window then serves one host slot a
call, each waited for, cycling over the lanes while the host's clock,
queue, memo and ensemble run on from boot; it counts the host slots whose
results were ready before the window closed, each worth the fleet's node
count of node-slots taken in and served.  Every slot's books are checked:
served + deadline misses + overflow drops + backlog = payloads taken in.

After the window the plain reference (``bench/host_reference.py``)
replays the same lanes from the same boot state over the window's first
``compared_slots`` host slots, and ``bench/host_compare.py`` compares.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import common, host_compare, host_reference
from bench.common import log, span
from bench.drivers.fleet import Fleet

COUNTERS = host_reference.COUNTERS


def host_config(cfg: dict):
    """The host's static configuration for a cell's configuration."""
    from repro.host import HostServeConfig
    m, h = cfg["model"], cfg["host"]
    return HostServeConfig(
        channels=m["channels"], k=cfg["k_max"], m=cfg["m_samples"],
        t=m["window"], n_classes=m["n_classes"], n_nodes=cfg["n_nodes"],
        batch_size=h["batch_size"], queue_capacity=h["queue_capacity"],
        cache_capacity=h["cache_capacity"], qos_slots=h["qos_slots"],
        batches_per_slot=h["batches_per_slot"], telemetry=h["telemetry"])


class Host:
    """Set-up state of one host cell: the fleet's lanes and the host's
    arguments."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        import jax

        from repro.core.recovery import GeneratorParams
        from repro.host import fleet_slot_entries
        from repro.serving import seeker_fleet_simulate

        if traffic["host_slots_per_call"] != 1:
            raise ValueError("the host driver serves one host slot a call")
        self.cfg, self.devices = cfg, devices
        self.precision = cfg["matmul_precision"]
        self.hcfg = host_config(cfg)
        self.compared = traffic["compared_slots"]
        fleet = Fleet(cfg, {"segment_slots": 1,
                            "horizon_slots": traffic["horizon_slots"],
                            "harvest_scale": traffic["harvest_scale"],
                            "sample_nodes": 1}, seed, devices)
        lane_fn = jax.jit(lambda cs, dec, alive: fleet_slot_entries(
            jax.tree_util.tree_map(lambda a: a[0], cs), dec[0], alive[0]))
        self.lanes, self.taken = [], []
        carry = fleet.boot()
        for wins, harv, labels in fleet.segments:
            state, keys, browned = carry
            with jax.default_matmul_precision(self.precision):
                res = seeker_fleet_simulate(
                    wins, harv, state0=state, node_keys=keys,
                    brownout_state0=browned, labels=labels, coresets=True,
                    **fleet.kwargs)
            lane = lane_fn(res["coresets"], res["decisions"], res["alive"])
            self.lanes.append(lane)
            self.taken.append(int(np.sum(np.asarray(lane[2]))))
            carry = (res["final_state"], res["final_keys"],
                     res["final_brownout"])
            del res
        common.block(self.lanes)
        self.weights = fleet.weights
        cls, gen = fleet.weights
        self.kwargs = dict(cfg=self.hcfg, host_params=cls,
                           gen_params=GeneratorParams(*gen),
                           base_key=common.seed_key(seed, 4), donate=True)
        self.n = fleet.n
        del fleet, carry

    def boot(self):
        from repro.host import host_server_init
        return host_server_init(self.hcfg)

    def slot(self, j: int, state):
        """Dispatch host slot ``j`` (cycling over the lanes) from ``state``;
        returns the new state and the slot's output, not yet computed."""
        import jax

        import repro.host
        entries, nids, mask = self.lanes[j % len(self.lanes)]
        with span("bench.dispatch"), jax.default_matmul_precision(
                self.precision):
            return repro.host.host_serve_slot(state, entries, nids, mask,
                                              **self.kwargs)


def _counters(state):
    import jax.numpy as jnp
    return jnp.stack([state.served, state.deadline_misses,
                      state.queue.drops_overflow,
                      jnp.sum(state.queue.valid.astype(jnp.int32)),
                      state.cache.hits, state.cache.misses])


def _keep(state, out):
    """What the comparison needs of a compared slot: its rows and a copy
    of the vote sums (the state is donated to the next slot)."""
    import jax.numpy as jnp
    return {"node_id": out.node_id, "deadline": out.deadline,
            "valid": out.valid, "cache_hit": out.cache_hit,
            "logits": out.logits, "votes": jnp.copy(state.ensemble_votes)}


def _jit(fn):
    import jax
    return jax.jit(fn)


_counters_fn = _jit(_counters)
_keep_fn = _jit(_keep)


def window(host: Host, seconds: float, state):
    """Serve host slots one after another for ``seconds``, each waited for
    (its counters copied to the host) before the next is dispatched;
    returns the counters of every slot, what the comparison keeps of the
    first ``compared`` slots, the time each slot was ready and the window
    start, and the last state."""
    import jax
    counters, kept, ready = [], [], []
    t0 = time.perf_counter()
    with span("bench.window"):
        j = 0
        while time.perf_counter() - t0 < seconds:
            state, out = host.slot(j, state)
            with span("bench.pick"):
                c = _counters_fn(state)
                if j < host.compared:
                    kept.append(_keep_fn(state, out))
            with span("bench.block"):
                counters.append(np.asarray(jax.device_get(c)))
            ready.append(time.perf_counter())
            j += 1
    return counters, kept, ready, t0, state


def book_failures(host: Host, counters) -> dict:
    """Slots whose books do not balance: served + deadline misses +
    overflow drops + backlog must equal the payloads taken in so far."""
    out, taken = {}, 0
    for j, c in enumerate(counters):
        taken += host.taken[j % len(host.taken)]
        served, missed, dropped, backlog = (int(x) for x in c[:4])
        if served + missed + dropped + backlog != taken:
            out[j] = (f"served {served} + missed {missed} + dropped "
                      f"{dropped} + backlog {backlog} != taken in {taken}")
    return out


def reference_slots(host: Host, n_slots: int,
                    precision: str | None = None) -> list:
    """The reference's record of the first ``n_slots`` host slots, with
    matrix products at ``precision`` (by default the configuration's)."""
    import jax
    lanes = []
    for j in range(n_slots):
        entries, nids, mask = jax.device_get(
            host.lanes[j % len(host.lanes)])
        lane = {k: np.asarray(v) for k, v in entries._asdict().items()}
        lane["node_id"], lane["mask"] = np.asarray(nids), np.asarray(mask)
        lanes.append(lane)
    h = host.hcfg
    numbers = {"batch_size": h.batch_size,
               "batches_per_slot": h.batches_per_slot,
               "queue_capacity": h.queue_capacity,
               "cache_capacity": h.cache_capacity, "qos_slots": h.qos_slots}
    return host_reference.replay(lanes, numbers, host.n, host.weights,
                                 host.kwargs["base_key"], h.t,
                                 precision or host.precision)


def run(cell: dict, cfg: dict, traffic: dict, limits: dict, seed: int,
        seconds: float, trace: bool, devices, t_start: float) -> dict:
    import jax

    host = Host(cfg, traffic, seed, devices)
    shares = [t / host.n for t in host.taken]
    log(f"lanes: {len(host.lanes)} fleet slots of {host.n} nodes, D3+D4 "
        f"payloads {host.taken} (mean share {np.mean(shares):.6f}); ready "
        f"{time.perf_counter() - t_start:.3f} s after start; set-up peak "
        f"{common.memory_peak(devices)} B")
    log(f"host: {host.hcfg}; sized for batches_per_slot "
        f"{math.ceil(1.25 * np.mean(host.taken) / host.hcfg.batch_size)}")

    # warm up: two slots from boot, then boot again
    state = host.boot()
    for j in range(2):
        state, out = host.slot(j, state)
        common.block((_counters_fn(state), _keep_fn(state, out)))
    del state, out
    state = host.boot()
    common.block(state)
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
    counters, kept, ready, t0, state = window(host, seconds, state)
    if trace:
        jax.profiler.stop_trace()
    jax.monitoring.clear_event_listeners()
    del state
    peak = common.memory_peak(devices)

    in_window = [r for r in ready if r - t0 <= seconds]
    rate = (len(in_window) * host.n / (in_window[-1] - t0)
            if in_window else 0.0)
    failures = book_failures(host, counters)
    log(f"window: {len(ready)} host slots, {len(in_window)} ready in "
        f"{seconds} s; {len(compiles)} compiles inside the window; peak "
        f"{peak} B")
    log("slots ready at (s after the window opened): "
        + " ".join(f"{r - t0:.4f}" for r in ready))
    last = dict(zip(COUNTERS, (int(x) for x in counters[-1])))
    log(f"counters after the last slot: {last}")

    prog = [{k: np.asarray(v) for k, v in jax.device_get(p).items()}
            for p in kept]
    for p, c in zip(prog, counters):
        p["counters"] = c
    ref = reference_slots(host, len(prog))
    numbers = host_compare.compare(prog, ref, limits["logit_gap"])
    correct, rows = host_compare.judge(numbers, limits)
    correct = correct and not failures
    for j, why in list(failures.items())[:8]:
        log(f"slot {j}: books broken: {why}")
    if failures:
        log(f"books broken in {len(failures)} of {len(ready)} slots")
    log("readings: " + ", ".join(f"{k} {v!r}" for k, v in numbers.items()))

    served_in_window = (int(counters[len(in_window) - 1][0])
                        if in_window else 0)
    return {
        "correct": correct, "attempted": len(ready),
        "failed": len(failures), "checks": rows, "numbers": numbers,
        "end_to_end": {"node_slots_per_s": rate, "setup_s": setup_s,
                       "peak_hbm_gb": peak / 1e9},
        "counts": {"slots": len(ready), "slots_in_window": len(in_window),
                   "served_in_window": served_in_window,
                   "counters": last, "taken": host.taken,
                   "compiles_in_window": len(compiles)},
        "slot_s": np.diff([t0] + in_window).tolist(),
        "window_s_host": (in_window[-1] - t0) if in_window else 0.0,
        "cfg": cfg, "devices": devices,
        **({"trace_dir": tdir} if trace else {}),
    }
