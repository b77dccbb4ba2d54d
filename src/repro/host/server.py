"""The host serve loop: queue -> EDF scheduler -> batched recovery -> DNN.

This is the host half of the paper's system, as a real serving subsystem
instead of per-payload inline work: wire-format payloads from the fleet are
stamped with QoS deadlines and pushed into the :mod:`repro.host.queue` ring
buffer; each serve slot the :mod:`repro.host.scheduler` assembles one or
more **fixed-shape** EDF microbatches; the batch is decoded
(:func:`repro.serving.edge_host.decode_wire_coresets` /
``decode_wire_samples``), recovered (cluster-ball resynthesis or the
GAN generator, selected per entry), and run through the full-precision HAR
DNN in one batched ``har_apply``; per-node results accumulate into a mean-
logit ensemble and a majority-vote histogram (the paper's multi-sensor
host ensemble).

Design points:

* **compile-cache stability** — every tensor entering the jitted slot has a
  shape fixed by :class:`HostServeConfig` (batch size, queue capacity,
  ingest width), never by fleet occupancy, so a churny trace compiles the
  slot ONCE.  :func:`serve_trace_count` exposes the trace counter the tests
  pin (acceptance: <= 2 distinct compiled shapes over a churny trace).
* **payload-deterministic recovery PRNG** — each payload's recovery key is
  ``fold_in(base_key, signature)``, so identical payloads recover
  identically and the :mod:`repro.host.cache` memo is *bitwise* equal to
  recomputation.  A batch whose live entries all hit skips recovery + DNN
  entirely (``lax.cond``), mirroring D0's skip on the node.
* **resumable carry** — :class:`HostServerState` is an explicit pytree
  carry, exactly like the fleet engine's ``state0``/``final_state``:
  chaining ``host_serve_slot``/``host_serve_trace`` calls continues the
  clock, queue backlog, cache and ensemble where the last call stopped.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..core.coreset import ClusterCoreset, SamplingCoreset
from ..core.decision import D3_CLUSTER, D4_SAMPLING
from ..core.recovery import (GeneratorParams, recover_cluster_window,
                             recover_sampling_window)
from ..models.har import har_apply
from ..obs import (MetricsSpec, compile_event, counter, counter_add,
                   gauge, gauge_set, hist_observe, histogram, metrics_init,
                   metrics_summary)
from ..obs import trace as obs_trace
from ..obs.compile_guard import compile_key_counts
from ..serving.edge_host import (WirePayload, WireSamplePayload,
                                 decode_wire_coresets, decode_wire_samples,
                                 encode_wire_coresets, encode_wire_samples)
from .cache import (RecoveryCache, cache_init, cache_insert_batch,
                    cache_lookup_batch, cache_stats, payload_signature)
from .queue import (PayloadQueue, queue_init, queue_occupancy,
                    queue_push_batch, queue_wait_slots)
from .scheduler import batch_wait_slots, edf_pop_batch

__all__ = ["HostServeConfig", "HostPayload", "HostServerState", "SlotOutput",
           "host_payload_example", "cluster_entries", "sampling_entries",
           "fleet_slot_entries",
           "host_server_init", "host_server_init_stacked", "host_serve_slot",
           "host_serve_trace", "host_telemetry_spec", "serve_fleet_payloads",
           "recover_infer_batch", "host_server_stats", "host_ensemble",
           "serve_trace_count"]

CLUSTER_KIND = 0    # D3 payload: quantized cluster coreset
SAMPLING_KIND = 1   # D4 payload: quantized importance samples + moments


@dataclasses.dataclass(frozen=True)
class HostServeConfig:
    """Static shape/QoS configuration of one host server (hashable: it keys
    the compile cache — one executable per config, reused across slots)."""

    channels: int               # sensor channels C
    k: int                      # clusters per channel (cluster payloads)
    m: int                      # samples per window (sampling payloads)
    t: int                      # window length the host recovers to
    n_classes: int
    n_nodes: int                # fleet size for the per-node ensemble
    batch_size: int = 64        # EDF microbatch rows (fixed shape)
    queue_capacity: int = 256   # ring-buffer slots (>= ingest width per slot)
    cache_capacity: int = 256   # recovery-memo entries
    qos_slots: int = 4          # deadline = arrival + qos_slots (inclusive)
    batches_per_slot: int = 1   # host service rate per slot
    telemetry: bool = False     # registry lanes + latency histograms in-slot
    n_tasks: int = 1            # mixed fleets: stacked per-task host DNNs

    def __post_init__(self):
        """Reject configurations that would silently corrupt service.

        ``batch_size > queue_capacity`` is the nasty one: ``edf_pop_batch``
        takes ``order[:batch_size]`` over the capacity-long slot array, so
        the batch clamps to ``queue_capacity`` rows and the configured
        service rate is a lie — every slot quietly serves fewer payloads
        than the config promises.  (An ingest lane wider than the capacity
        is the call-time analogue: the lane can overflow every slot — see
        :func:`host_serve_slot`.)"""
        for field in ("channels", "k", "m", "t", "n_classes", "n_nodes",
                      "batch_size", "queue_capacity", "cache_capacity",
                      "n_tasks"):
            v = getattr(self, field)
            if v < 1:
                raise ValueError(
                    f"HostServeConfig.{field} must be >= 1, got {v}")
        # qos_slots=0 is serve-this-slot-or-miss; batches_per_slot=0 is the
        # normalized compile-probe key (serve_trace_count) — both legal
        for field in ("qos_slots", "batches_per_slot"):
            v = getattr(self, field)
            if v < 0:
                raise ValueError(
                    f"HostServeConfig.{field} must be >= 0, got {v}")
        if self.batch_size > self.queue_capacity:
            raise ValueError(
                f"HostServeConfig.batch_size={self.batch_size} exceeds "
                f"queue_capacity={self.queue_capacity}: edf_pop_batch can "
                f"only assemble queue_capacity rows, so the extra "
                f"{self.batch_size - self.queue_capacity} batch rows would "
                f"silently never be filled — raise queue_capacity or lower "
                f"batch_size")


class HostPayload(NamedTuple):
    """One queue entry's payload: the union of the two wire formats, with a
    ``kind`` discriminator (all branches traced, selection by mask — the
    repo-wide pattern for static shapes).  Unused half is zeros.

    ``task`` selects which workload's host DNN serves the entry in a
    mixed-task fleet (``HostServeConfig.n_tasks > 1``); homogeneous
    deployments leave it 0.  It is an ordinary payload leaf, so
    :func:`repro.host.cache.payload_signature` hashes it with everything
    else — the same coreset from a HAR node and a bearing node can never
    collide in the recovery memo."""

    kind: jnp.ndarray       # () int8 — CLUSTER_KIND | SAMPLING_KIND
    # D3: quantized cluster coreset (codes + dequantization ranges)
    c_codes: jnp.ndarray    # (C, k, 2) int16
    r_codes: jnp.ndarray    # (C, k) int8
    n_codes: jnp.ndarray    # (C, k) int8
    c_lo: jnp.ndarray       # () float32
    c_hi: jnp.ndarray       # () float32
    c_rhi: jnp.ndarray      # () float32
    # D4: quantized importance samples + GAN conditioning moments
    s_idx: jnp.ndarray      # (m,) int8
    s_codes: jnp.ndarray    # (m, C) int16
    s_lo: jnp.ndarray       # () float32
    s_hi: jnp.ndarray       # () float32
    s_mean: jnp.ndarray     # (C,) float32
    s_var: jnp.ndarray      # (C,) float32
    # heterogeneous fleets: which workload's DNN answers this entry
    task: jnp.ndarray       # () int8 — index into stacked per-task params


class SlotOutput(NamedTuple):
    """Per-slot served results: ``batches_per_slot * batch_size`` rows in
    EDF service order; padding rows have ``valid=False``."""

    node_id: jnp.ndarray    # (Bq,) int32
    logits: jnp.ndarray     # (Bq, L) float32
    deadline: jnp.ndarray   # (Bq,) int32
    cache_hit: jnp.ndarray  # (Bq,) bool
    valid: jnp.ndarray      # (Bq,) bool


class HostServerState(NamedTuple):
    """The resumable serve-loop carry (cf. the fleet engine's state0)."""

    queue: PayloadQueue
    cache: RecoveryCache
    slot: jnp.ndarray             # () int32 — host clock
    served: jnp.ndarray           # () int32 — payloads answered in time
    deadline_misses: jnp.ndarray  # () int32 — expired before service
    ensemble_logits: jnp.ndarray  # (n_nodes, L) float32 — summed logits
    ensemble_votes: jnp.ndarray   # (n_nodes, L) int32 — argmax histogram
    # registry lanes (cfg.telemetry=True; None = untelemetered, an empty
    # pytree node, so every legacy positional construction still works)
    metrics: Any = None


def host_payload_example(cfg: HostServeConfig) -> HostPayload:
    """Zero entry pytree defining the queue's slot shapes."""
    c, k, m = cfg.channels, cfg.k, cfg.m
    z = jnp.zeros
    return HostPayload(
        kind=z((), jnp.int8),
        c_codes=z((c, k, 2), jnp.int16), r_codes=z((c, k), jnp.int8),
        n_codes=z((c, k), jnp.int8), c_lo=z(()), c_hi=z(()), c_rhi=z(()),
        s_idx=z((m,), jnp.int8), s_codes=z((m, c), jnp.int16),
        s_lo=z(()), s_hi=z(()), s_mean=z((c,)), s_var=z((c,)),
        task=z((), jnp.int8))


def _entry_tasks(tasks, b: int) -> jnp.ndarray:
    """(B,) int8 task column for a batch of entries; ``None`` = task 0."""
    if tasks is None:
        return jnp.zeros((b,), jnp.int8)
    return jnp.asarray(tasks).reshape(b).astype(jnp.int8)


def cluster_entries(wire: WirePayload, m: int,
                    tasks: jnp.ndarray | None = None) -> HostPayload:
    """Batched D3 entries from a quantized cluster wire payload (the tensors
    :func:`repro.serving.edge_host.fleet_serve_step` gathers).  ``tasks`` is
    the optional (B,) per-entry task id of a mixed fleet."""
    b, c, _, _ = wire.c_codes.shape
    z = jnp.zeros
    return HostPayload(
        kind=z((b,), jnp.int8),
        c_codes=wire.c_codes, r_codes=wire.r_codes, n_codes=wire.n_codes,
        c_lo=wire.lo.reshape(b), c_hi=wire.hi.reshape(b),
        c_rhi=wire.rhi.reshape(b),
        s_idx=z((b, m), jnp.int8), s_codes=z((b, m, c), jnp.int16),
        s_lo=z((b,)), s_hi=z((b,)), s_mean=z((b, c)), s_var=z((b, c)),
        task=_entry_tasks(tasks, b))


def sampling_entries(swire: WireSamplePayload, k: int,
                     tasks: jnp.ndarray | None = None) -> HostPayload:
    """Batched D4 entries from a quantized sampling wire payload."""
    b, m = swire.idx.shape
    c = swire.v_codes.shape[-1]
    z = jnp.zeros
    return HostPayload(
        kind=jnp.full((b,), SAMPLING_KIND, jnp.int8),
        c_codes=z((b, c, k, 2), jnp.int16), r_codes=z((b, c, k), jnp.int8),
        n_codes=z((b, c, k), jnp.int8), c_lo=z((b,)), c_hi=z((b,)),
        c_rhi=z((b,)),
        s_idx=swire.idx, s_codes=swire.v_codes,
        s_lo=swire.lo.reshape(b), s_hi=swire.hi.reshape(b),
        s_mean=swire.mean, s_var=swire.var,
        task=_entry_tasks(tasks, b))


def fleet_slot_entries(coresets: dict, decisions: jnp.ndarray,
                       alive: jnp.ndarray
                       ) -> tuple[HostPayload, jnp.ndarray, jnp.ndarray]:
    """One fleet slot's payloads as the host's fixed-width ingest lane.

    ``coresets`` is one slot of the fleet engine's ``res["coresets"]``
    (leaves with leading node axis N), ``decisions`` and ``alive`` the
    slot's (N,) decision and alive traces.  Row ``i`` is node ``i``'s
    payload through the wire encoders: a D3 node's cluster coreset as a
    ``CLUSTER_KIND`` entry, a D4 node's samples as a ``SAMPLING_KIND``
    entry.  Returns ``(entries, node_ids, mask)`` for
    :func:`host_serve_slot`; ``mask`` is alive and D3-or-D4, so nodes that
    sent nothing, or only a label, never enqueue."""
    k = coresets["centers"].shape[-2]
    m = coresets["samp_idx"].shape[-1]
    cluster = cluster_entries(encode_wire_coresets(
        coresets["centers"], coresets["radii"], coresets["counts"]), m)
    sampling = sampling_entries(encode_wire_samples(
        coresets["samp_idx"], coresets["samp_vals"], coresets["samp_mean"],
        coresets["samp_var"]), k)
    is_d4 = decisions == D4_SAMPLING
    entries = jax.tree_util.tree_map(
        lambda c, s: jnp.where(is_d4.reshape((-1,) + (1,) * (c.ndim - 1)),
                               s, c), cluster, sampling)
    mask = alive & ((decisions == D3_CLUSTER) | is_d4)
    return entries, jnp.arange(decisions.shape[0], dtype=jnp.int32), mask


@functools.lru_cache(maxsize=32)
def _host_spec(qos_slots: int) -> MetricsSpec:
    # sojourn of a SERVED payload is 0..qos_slots (later pops expire first);
    # end-to-end latency (arrival -> result available) is sojourn + 1.  Small
    # deadline windows get exact per-slot categorical bins; large ones fall
    # back to 16 log-spaced bins over the feasible span.
    span = qos_slots + 1
    if span + 2 <= 18:
        lat = functools.partial(histogram, bins=span + 2, log=False,
                                unit="slots")
    else:
        lat = functools.partial(histogram, bins=16, lo=1.0, hi=float(span),
                                unit="slots")
    return MetricsSpec((
        counter("host.served", "payloads"),
        counter("host.deadline_misses", "payloads"),
        counter("host.drops_overflow", "payloads"),
        counter("host.cache_hits", "lookups"),
        counter("host.cache_misses", "lookups"),
        gauge("host.backlog", "payloads"),
        lat("host.sojourn_slots"),
        lat("host.e2e_slots"),
        lat("host.sojourn_slots.cluster"),
        lat("host.sojourn_slots.sampling"),
        lat("host.backlog_age_slots"),
    ))


def host_telemetry_spec(cfg: HostServeConfig) -> MetricsSpec:
    """The host tier's registry lanes: QoS counters (served / misses /
    drops / cache), a backlog gauge, and the fixed-bin latency histograms
    QoS percentiles are extracted from — per-payload queue sojourn,
    end-to-end slot latency, per-payload-class sojourn breakdown, and the
    age profile of the waiting backlog.  Pure function of ``cfg.qos_slots``
    (the only field that shapes the bins), so service-rate variants of one
    config share the spec instance."""
    return _host_spec(cfg.qos_slots)


def host_server_init(cfg: HostServeConfig) -> HostServerState:
    return HostServerState(
        queue=queue_init(host_payload_example(cfg), cfg.queue_capacity),
        cache=cache_init(cfg.cache_capacity, cfg.n_classes),
        slot=jnp.zeros((), jnp.int32),
        served=jnp.zeros((), jnp.int32),
        deadline_misses=jnp.zeros((), jnp.int32),
        ensemble_logits=jnp.zeros((cfg.n_nodes, cfg.n_classes), jnp.float32),
        ensemble_votes=jnp.zeros((cfg.n_nodes, cfg.n_classes), jnp.int32),
        metrics=(metrics_init(host_telemetry_spec(cfg)) if cfg.telemetry
                 else None))


def host_server_init_stacked(cfg: HostServeConfig,
                             n_hosts: int) -> HostServerState:
    """``n_hosts`` independent server states stacked on a leading axis —
    the carry of :func:`repro.serving.edge_host.fleet_serve_step`'s
    per-shard host mode (one host server per node shard, the ROADMAP
    multi-host shape on one process)."""
    if n_hosts < 1:
        raise ValueError(f"n_hosts must be >= 1, got {n_hosts}")
    one = host_server_init(cfg)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n_hosts,) + a.shape).copy(), one)


# ---------------------------------------------------------------------------
# Batched recovery + inference (the host DNN path)
# ---------------------------------------------------------------------------

def recover_infer_batch(payload: WirePayload, host_params: dict,
                        keys: jax.Array, t: int) -> jnp.ndarray:
    """Dequantize a cluster wire-payload batch, recover windows, run the
    full-precision DNN -> (B, n_classes) logits.  The batched host compute
    shared by the serving tiers (:func:`edge_host_serve_step`,
    :func:`fleet_serve_step`) and this server's miss path."""
    centers, radii, counts = decode_wire_coresets(payload)
    wins = jax.vmap(lambda c, r, n, kk: recover_cluster_window(
        ClusterCoreset(c, r, n), kk, t))(centers, radii, counts, keys)
    return har_apply(host_params, wins)


def _entry_windows(p: HostPayload, gen_params: GeneratorParams,
                   keys: jax.Array, t: int, valid: jnp.ndarray
                   ) -> jnp.ndarray:
    """Recover a (B, T, C) window batch from mixed-kind entries.

    Both recovery paths are traced, but a ``lax.switch`` on the batch's kind
    mix runs only what the batch needs at runtime: a single-kind microbatch
    (the common case — a fleet round is all-D3) skips the other recovery
    entirely; only genuinely mixed batches compute both and select per
    entry.  Rows with ``valid=False`` count as neither kind.
    """
    b = p.kind.shape[0]

    def cluster_windows(_):
        wire = WirePayload(p.c_codes, p.r_codes, p.n_codes,
                           p.c_lo.reshape(b, 1, 1, 1),
                           p.c_hi.reshape(b, 1, 1, 1),
                           p.c_rhi.reshape(b, 1, 1))
        centers, radii, counts = decode_wire_coresets(wire)
        return jax.vmap(lambda c, r, n, kk: recover_cluster_window(
            ClusterCoreset(c, r, n), kk, t))(centers, radii, counts, keys)

    def sampling_windows(_):
        swire = WireSamplePayload(p.s_idx, p.s_codes, p.s_lo.reshape(b, 1, 1),
                                  p.s_hi.reshape(b, 1, 1), p.s_mean, p.s_var)
        idx, vals, mean, var = decode_wire_samples(swire)
        return jax.vmap(
            lambda i, v, mu, va, kk: recover_sampling_window(
                gen_params,
                SamplingCoreset(i, v, jnp.ones_like(i, jnp.float32), mu, va),
                kk, t))(idx, vals, mean, var, keys)

    def mixed(_):
        return jnp.where((p.kind == CLUSTER_KIND)[:, None, None],
                         cluster_windows(None), sampling_windows(None))

    has_sampling = jnp.any(valid & (p.kind == SAMPLING_KIND))
    has_cluster = jnp.any(valid & (p.kind == CLUSTER_KIND))
    branch = jnp.where(has_sampling & has_cluster, 2,
                       jnp.where(has_sampling, 1, 0))
    return jax.lax.switch(branch, [cluster_windows, sampling_windows, mixed],
                          None)


def _check_lane_width(cfg: HostServeConfig, width: int) -> None:
    """An ingest lane wider than the ring would overflow EVERY slot — even
    an empty queue cannot hold the arrivals, so the excess is guaranteed
    drops by construction, not by load.  Rejected at the entry points
    (static shape, so a python check; the config-level analogue lives in
    :meth:`HostServeConfig.__post_init__`)."""
    if width > cfg.queue_capacity:
        raise ValueError(
            f"ingest lane of {width} entries exceeds queue_capacity="
            f"{cfg.queue_capacity}: even an empty queue would overflow on "
            f"every slot — raise HostServeConfig.queue_capacity or narrow "
            f"the lane")


# ---------------------------------------------------------------------------
# The jitted serve slot
# ---------------------------------------------------------------------------

# the compile-cache acceptance probe rides the generalized trace-event
# accounting of repro.obs.compile_guard: serve builders emit
# compile_event("host.serve", (cfg, tag)) at trace time — once per distinct
# compiled shape — and serve_trace_count groups those keys the way the
# host tests have always pinned them
_SERVE_COMPONENT = "host.serve"


def serve_trace_count(cfg: HostServeConfig | None = None) -> int:
    """How many times serve functions were traced (== compiled shapes).

    With ``cfg``, counts every trace for that config *including* its
    service-rate variants (``batches_per_slot`` differences — e.g. the
    config :func:`serve_fleet_payloads` derives per fleet round): a variant
    is a distinct compiled shape and must show up in the probe.  Without
    ``cfg``, the global total."""
    counts = compile_key_counts(_SERVE_COMPONENT)
    if cfg is not None:
        key = dataclasses.replace(cfg, batches_per_slot=0)
        return sum(
            n for (c, _), n in counts.items()
            if dataclasses.replace(c, batches_per_slot=0) == key)
    return sum(counts.values())


def _serve_batch(cfg: HostServeConfig, tel: MetricsSpec | None, now,
                 carry: tuple, host_params: dict,
                 gen_params: GeneratorParams, base_key: jax.Array
                 ) -> tuple[tuple, SlotOutput]:
    """One EDF microbatch through cache + recovery + DNN + ensemble.
    ``carry`` = (queue, cache, served, deadline misses, ensemble logits,
    ensemble votes, metrics)."""
    queue, cache, served, missed_total, ens_l, ens_v, metrics = carry
    with obs_trace.scope("host.edf"):
        queue, batch, missed = edf_pop_batch(queue, cfg.batch_size, now=now)
        missed_total = missed_total + missed
        if tel is not None:
            # QoS observables at service time: queue sojourn of every row
            # served this batch (and its +1-slot end-to-end latency), with a
            # per-payload-class breakdown — the histograms the p50/p95/p99
            # extraction reads
            sojourn = batch_wait_slots(batch, now)
            is_cluster = batch.valid & (batch.payload.kind == CLUSTER_KIND)
            is_sampling = batch.valid & (batch.payload.kind == SAMPLING_KIND)
            metrics = hist_observe(tel, metrics, "host.sojourn_slots",
                                   sojourn, batch.valid)
            metrics = hist_observe(tel, metrics, "host.e2e_slots",
                                   sojourn + 1, batch.valid)
            metrics = hist_observe(tel, metrics, "host.sojourn_slots.cluster",
                                   sojourn, is_cluster)
            metrics = hist_observe(tel, metrics,
                                   "host.sojourn_slots.sampling",
                                   sojourn, is_sampling)
            metrics = counter_add(tel, metrics, "host.served", batch.valid)
            metrics = counter_add(tel, metrics, "host.deadline_misses",
                                  missed)

    with obs_trace.scope("host.cache"):
        sigs = jax.vmap(payload_signature)(batch.payload)        # (B, 2)
        hit, cached = cache_lookup_batch(cache, sigs, batch.valid)

    def compute(_):
        with obs_trace.scope("host.recover"):
            keys = jax.vmap(
                lambda s: jax.random.fold_in(
                    jax.random.fold_in(base_key, s[0]), s[1]))(sigs)
            wins = _entry_windows(batch.payload, gen_params, keys, cfg.t,
                                  batch.valid)
        with obs_trace.scope("host.dnn"):
            if cfg.n_tasks == 1:
                return har_apply(host_params, wins)
            # mixed fleets: run every task's DNN over the batch at fixed
            # shape (host_params arrives stacked leaf-wise, leading axis
            # n_tasks — the kind-switch pattern, one level up), then gather
            # each entry's own task row
            per_task = jax.vmap(lambda p: har_apply(p, wins))(host_params)
            tid = jnp.clip(batch.payload.task.astype(jnp.int32),
                           0, cfg.n_tasks - 1)
            return per_task[tid, jnp.arange(tid.shape[0])]

    with obs_trace.scope("host.cache"):
        # a fully-memoized batch skips recovery + DNN (the host-side D0 skip)
        all_hit = jnp.all(hit | ~batch.valid)
        logits = jax.lax.cond(all_hit, lambda _: cached, compute, None)
        logits = jnp.where(hit[:, None], cached, logits)

        fresh = batch.valid & ~hit
        cache = cache_insert_batch(cache, sigs, logits, fresh)
        cache = cache._replace(
            hits=cache.hits + jnp.sum(hit.astype(jnp.int32)),
            misses=cache.misses + jnp.sum(fresh.astype(jnp.int32)))
        if tel is not None:
            metrics = counter_add(tel, metrics, "host.cache_hits", hit)
            metrics = counter_add(tel, metrics, "host.cache_misses", fresh)

    with obs_trace.scope("host.ensemble"):
        served = served + jnp.sum(batch.valid.astype(jnp.int32))
        # per-node ensemble: mean-logit sum + majority-vote histogram
        nid = jnp.clip(jnp.where(batch.valid, batch.node_id, 0),
                       0, cfg.n_nodes - 1)
        w = batch.valid.astype(jnp.float32)[:, None]
        ens_l = ens_l.at[nid].add(logits * w)
        votes = (jax.nn.one_hot(jnp.argmax(logits, axis=-1), cfg.n_classes,
                                dtype=jnp.int32)
                 * batch.valid[:, None].astype(jnp.int32))
        ens_v = ens_v.at[nid].add(votes)
    return ((queue, cache, served, missed_total, ens_l, ens_v, metrics),
            SlotOutput(batch.node_id, logits, batch.deadline, hit,
                       batch.valid))


def _slot_body(cfg: HostServeConfig, state: HostServerState,
               entries: HostPayload, node_ids: jnp.ndarray,
               mask: jnp.ndarray, host_params: dict,
               gen_params: GeneratorParams, base_key: jax.Array
               ) -> tuple[HostServerState, SlotOutput]:
    """One serve slot: ingest stamped arrivals, then run
    ``cfg.batches_per_slot`` EDF microbatches through cache + recovery +
    DNN.  The microbatches are one ``lax.scan``, so the program does not
    grow with ``batches_per_slot``.  Pure function of fixed-shape
    inputs."""
    tel = host_telemetry_spec(cfg) if cfg.telemetry else None
    metrics = state.metrics
    if tel is not None and metrics is None:
        raise ValueError(
            "cfg.telemetry=True but the server state has no metrics lanes — "
            "build the state with host_server_init(cfg) using the SAME "
            "telemetry setting (the lanes are part of the resumable carry)")
    with obs_trace.scope("host.ingest"):
        arrival = jnp.broadcast_to(state.slot, node_ids.shape)
        deadline = arrival + cfg.qos_slots
        queue, _ = queue_push_batch(state.queue, entries, node_ids, arrival,
                                    deadline, mask)
        if tel is not None:
            metrics = counter_add(
                tel, metrics, "host.drops_overflow",
                queue.drops_overflow - state.queue.drops_overflow)

    carry = (queue, state.cache, state.served, state.deadline_misses,
             state.ensemble_logits, state.ensemble_votes, metrics)
    carry, outs = jax.lax.scan(
        lambda c, _: _serve_batch(cfg, tel, state.slot, c, host_params,
                                  gen_params, base_key),
        carry, None, length=cfg.batches_per_slot)
    queue, cache, served, missed_total, ens_l, ens_v, metrics = carry
    out = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), outs)
    if tel is not None:
        with obs_trace.scope("host.edf"):
            # backlog level and age profile AFTER this slot's service — what
            # is still waiting, and for how long it has waited
            metrics = gauge_set(tel, metrics, "host.backlog",
                                queue_occupancy(queue))
            metrics = hist_observe(tel, metrics, "host.backlog_age_slots",
                                   queue_wait_slots(queue, state.slot),
                                   queue.valid)
    new_state = HostServerState(queue, cache, state.slot + 1, served,
                                missed_total, ens_l, ens_v, metrics)
    return new_state, out


@functools.lru_cache(maxsize=32)
def _build_serve_slot(cfg: HostServeConfig, donate: bool):
    def slot(state, entries, node_ids, mask, host_params, gen_params,
             base_key):
        compile_event(_SERVE_COMPONENT, (cfg, "slot"))    # trace-time only
        obs_trace.instant("compile:host.serve_slot")
        return _slot_body(cfg, state, entries, node_ids, mask, host_params,
                          gen_params, base_key)
    return jax.jit(slot, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=32)
def _build_serve_trace(cfg: HostServeConfig, donate: bool):
    def trace(state, entries, node_ids, masks, host_params, gen_params,
              base_key):
        compile_event(_SERVE_COMPONENT, (cfg, "trace"))   # trace-time only
        obs_trace.instant("compile:host.serve_trace")

        def step(carry, inp):
            e, nid, m = inp
            return _slot_body(cfg, carry, e, nid, m, host_params, gen_params,
                              base_key)

        return jax.lax.scan(step, state, (entries, node_ids, masks))
    return jax.jit(trace, donate_argnums=(0,) if donate else ())


def host_serve_slot(state: HostServerState, entries: HostPayload,
                    node_ids: jnp.ndarray, mask: jnp.ndarray, *,
                    cfg: HostServeConfig, host_params: dict,
                    gen_params: GeneratorParams, base_key: jax.Array,
                    donate: bool = False
                    ) -> tuple[HostServerState, SlotOutput]:
    """Streaming entry point: one serve slot over a fixed-width ingest lane.

    ``entries`` leaves have leading axis A (the lane width — pad a churny
    slot's arrivals up to a FIXED A and mask the padding; a varying A would
    recompile).  Returns ``(state', SlotOutput)``; feed ``state'`` back in —
    backlog, cache, clock and ensemble all carry over.  The host spans
    ``host.prep`` (argument checks and conversion) and ``host.run`` (the
    compiled slot's dispatch) mark the call on the profiler's clock."""
    with obs_trace.span("host.prep", cat="host"):
        _check_lane_width(cfg, entries.kind.shape[0])
        run = _build_serve_slot(cfg, donate)
        node_ids = jnp.asarray(node_ids, jnp.int32)
        mask = jnp.asarray(mask, bool)
    with obs_trace.span("host.run", cat="host"):
        return run(state, entries, node_ids, mask, host_params, gen_params,
                   base_key)


def host_serve_trace(state: HostServerState, entries: HostPayload,
                     node_ids: jnp.ndarray, masks: jnp.ndarray, *,
                     cfg: HostServeConfig, host_params: dict,
                     gen_params: GeneratorParams, base_key: jax.Array,
                     donate: bool = False
                     ) -> tuple[HostServerState, SlotOutput]:
    """Whole-trace entry point: ``lax.scan`` of the serve slot over S slots
    (entry leaves (S, A, ...), masks (S, A)) in ONE compiled program.
    Resumable exactly like the fleet engine: chaining two traces through the
    returned state equals one long trace."""
    _check_lane_width(cfg, entries.kind.shape[1])
    run = _build_serve_trace(cfg, donate)
    return run(state, entries, jnp.asarray(node_ids, jnp.int32),
               jnp.asarray(masks, bool), host_params, gen_params, base_key)


def serve_fleet_payloads(state: HostServerState, wire: WirePayload,
                         node_ids: jnp.ndarray, *, cfg: HostServeConfig,
                         host_params: dict, gen_params: GeneratorParams,
                         base_key: jax.Array,
                         mask: jnp.ndarray | None = None,
                         node_tasks: jnp.ndarray | None = None,
                         donate: bool = False
                         ) -> tuple[HostServerState, SlotOutput]:
    """Ingest one fleet round of gathered cluster payloads (what
    :func:`repro.serving.edge_host.fleet_serve_step` all_gathers) and serve
    enough EDF microbatches to cover them at the configured batch size.

    ``mask`` is the round's alive mask (B,) — a churny fleet's dead nodes
    produce no radio frame, so their lane rows never enqueue (the lane stays
    at the FIXED fleet width; only the mask varies, which never re-traces).

    ``node_tasks`` is the round's (B,) per-node task ids for a mixed fleet
    (``cfg.n_tasks > 1`` + stacked ``host_params``): each payload is served
    by its own workload's recovery DNN.
    """
    entries = cluster_entries(wire, cfg.m, tasks=node_tasks)
    b = entries.kind.shape[0]
    if b > cfg.queue_capacity:
        raise ValueError(
            f"fleet round of {b} payloads exceeds queue capacity "
            f"{cfg.queue_capacity}; raise HostServeConfig.queue_capacity")
    n_batches = -(-b // cfg.batch_size)
    cfg = dataclasses.replace(cfg, batches_per_slot=n_batches)
    mask = jnp.ones((b,), bool) if mask is None else jnp.asarray(mask, bool)
    return host_serve_slot(state, entries, node_ids, mask, cfg=cfg,
                           host_params=host_params, gen_params=gen_params,
                           base_key=base_key, donate=donate)


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

def host_server_stats(state: HostServerState,
                      cfg: HostServeConfig | None = None) -> dict:
    """QoS counters as python numbers (one sync; call off the hot path).

    With ``cfg`` (and a state whose carry holds telemetry lanes), the dict
    additionally reports the QoS percentiles the ROADMAP asks for —
    ``sojourn_p50/p95/p99`` and ``e2e_p50/p95/p99`` slot latencies extracted
    from the in-slot histograms — plus the full
    :func:`repro.obs.metrics_summary` under ``"telemetry"``."""
    served = int(state.served)
    missed = int(state.deadline_misses)
    dropped = int(state.queue.drops_overflow)
    total = served + missed + dropped
    out = {
        "slot": int(state.slot),
        "served": served,
        "deadline_misses": missed,
        "drops_overflow": dropped,
        "backlog": int(queue_occupancy(state.queue)),
        "deadline_miss_rate": missed / max(total, 1),
        "qos_fail_rate": (missed + dropped) / max(total, 1),  # misses + drops
        **cache_stats(state.cache),
    }
    if cfg is not None and cfg.telemetry and state.metrics is not None:
        spec = host_telemetry_spec(cfg)
        summary = metrics_summary(spec, state.metrics)
        out["telemetry"] = summary
        for key, lane in (("sojourn", "host.sojourn_slots"),
                          ("e2e", "host.e2e_slots")):
            for q in (50, 95, 99):
                out[f"{key}_p{q}"] = summary[lane][f"p{q}"]
    return out


def host_ensemble(state: HostServerState) -> dict:
    """Per-node ensemble answers from the accumulated serve history:
    ``pred_mean`` (argmax of summed logits — the paper's logit ensemble) and
    ``pred_vote`` (majority vote over per-payload argmaxes), plus per-node
    served counts.  Nodes never served predict class 0 with count 0."""
    counts = jnp.sum(state.ensemble_votes, axis=-1)            # (N,)
    mean_logits = state.ensemble_logits \
        / jnp.maximum(counts, 1)[:, None].astype(jnp.float32)
    return {
        "counts": counts,
        "mean_logits": mean_logits,
        "pred_mean": jnp.argmax(mean_logits, axis=-1),
        "pred_vote": jnp.argmax(state.ensemble_votes, axis=-1),
    }
