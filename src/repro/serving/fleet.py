"""Fleet-scale batched Seeker simulator, single-device and sharded.

The single-node simulation (:func:`repro.serving.edge_host.seeker_simulate`)
models one EH-WSN; production serving means *fleets* — thousands of
independent sensor nodes (n_sensors x n_devices), each with its own
supercapacitor charge, harvest modality, predictor history, and memoization
phase.  :func:`seeker_fleet_simulate` runs all of them in ONE jitted
``lax.scan`` over time:

* the carry is a *stacked* ``SeekerNodeState`` (leading node axis N) plus a
  per-node PRNG key array — node ``i``'s stream is ``fold_in(key, i)``, so a
  fleet of N nodes is bit-compatible with N independent single-node runs;
* inside the step, the memoization hot path runs once for the whole fleet
  through the batched :func:`repro.kernels.signature_corr_op`
  ((N, T, C) x (L, T, C) -> (N, L); Pallas MXU kernel on TPU, the validated
  jnp oracle elsewhere), and the rest of the paper's Fig.-8 flow is
  ``jax.vmap`` of the per-node step — no Python loop over nodes anywhere;
* the scan carry is donated to the jitted run, so the stacked node state is
  updated in place across time steps instead of being reallocated.

:func:`seeker_fleet_simulate_sharded` scales the node axis past one device:
the stacked state, per-node keys, harvest traces and (N, S, T, C) window
streams are split over the mesh axes the ``"nodes"`` logical axis resolves
to (:data:`repro.sharding.FLEET_RULES`: ("pod", "data")) via
``shard_map_compat``, and the *entire* scan runs inside the manual region —
node state never leaves its shard.  Only fleet-level aggregates (bytes on
wire, the decision histogram, accuracy counts) cross shards, as ``psum``
scalars.  Fleets that don't divide the mesh quantum are padded with *inert*
nodes (zero harvest, masked out of every aggregate) and the padding is
sliced off the returned traces, so sharded results are bit-identical to the
single-device engine for any N.

Harvest traces are per-node (shape (N, S)): heterogeneous energy income is
the point of fleet simulation — per-node energy dynamics diverge (Gobieski et
al., arXiv:1810.07751), and the Seeker companion evaluation (arXiv:2204.13106)
runs exactly such heterogeneous wearable fleets.

**Churn** (node dropout/rejoin): harvested fleets are intermittent — nodes
brown out and rejoin mid-deployment.  Both engines accept an ``alive``
(N, S) bool trace (:func:`repro.core.energy.fleet_alive_traces`): in a dead
slot a node harvests nothing, holds its state *frozen* (supercapacitor
charge, predictor history, AAC continuity AND its PRNG stream), and emits
DEFER with a zero payload; on rejoin it continues exactly where it stopped —
no re-padding, no re-tracing, no shape change.  Every fleet aggregate
(bytes on wire, decision histogram, completion, accuracy) respects the
time-varying alive mask, not just the static padding mask.  An all-True
``alive`` is bitwise-identical to not passing one.

**Endogenous brown-out** (``brownout=BrownoutConfig(...)``): churn driven by
the simulated physics instead of an input array.  The decision ladder
switches to strict store-and-execute accounting — a decision must be
payable from ``stored + harvested_this_slot`` alone (the forecast still
ranks AAC's k but can no longer mint energy, and
:func:`repro.core.energy.supercap_step_direct` never clip-forgives debt) —
and the per-slot alive lane becomes ``exogenous_trace ∧ ¬browned_out``,
where ``browned_out`` lives in the scan carry and flips via supercap
hysteresis: below ``off_uj`` the node powers down (browned-out slots reuse
the dead-slot lane above, except the harvester keeps trickle-charging the
supercap), and at ``restart_uj`` it reboots into its frozen state.  The
engines emit the resulting ``alive``/``brownout`` (S, N) lanes plus
``brownout_slots``/``brownout_events`` counters (psum'd in the sharded
engine; padding nodes are exogenously dead and never brown "in").
``brownout=None`` keeps today's engines bitwise.

**Streaming** (:func:`seeker_fleet_simulate_streamed`): window streams are
fed to the scan in ``(chunk,)``-slot segments through the ``state0`` /
``node_keys`` resume contract (documented in docs/RESUME_CONTRACT.md), so
peak window memory is O(N·chunk·T·C) instead of O(N·S·T·C) while traces
stay bitwise-equal to one long run.

**Telemetry** (``telemetry=True`` or a :class:`repro.obs.MetricsSpec`): the
observability lane.  A metrics pytree (:func:`fleet_telemetry_spec`:
exact-int counters as normalized (2,) int32 ``[hi, lo]`` pairs, the
categorical decision histogram, a stored-energy gauge) rides the scan carry
of all three engines, updated per slot from the same masked quantities the
post-scan aggregates use; the sharded engine ``psum``-s the lanes
component-wise (int adds are associative, so lanes are *bitwise-equal*
across single-device, sharded and streamed runs), and the streamed driver
chains segments through ``telemetry_state0`` /
``res["telemetry"]`` (:func:`repro.obs.metrics_merge`) exactly like the
rest of the resume contract.  ``telemetry=None`` (default) keeps every
engine bitwise-identical to the untelemetered path — observation never
perturbs simulation.

**Intermittent inference** (``intermittent=IntermittentConfig(...)``): the
partial-inference lane.  Slots the strict ladder would DEFER instead run as
many energy-quantized stages of the on-node quantized DNN as ``stored +
harvested`` affords (:meth:`repro.core.energy.EnergyCosts.stage_costs`),
suspending the staged activations *in the scan carry*
(:class:`repro.serving.edge_host.IntermittentState` — a fourth carry lane
riding the ``state0``/``node_keys`` resume contract bitwise through brown-
outs and streamed segment boundaries).  An in-flight inference resumes
before new work starts; completion transmits at full depth (D8), and when
the remaining stages are unaffordable a confidence-tagged early-exit result
from the auxiliary head (D7) replaces the freeze-and-lose DEFER.  The lane
requires ``aux_params`` (:func:`repro.models.har.har_aux_init`) and
switches the ladder to strict store-and-execute accounting like
``brownout`` does.  ``intermittent=None`` keeps all three engines bitwise.

**One scan body, registered lanes** (:data:`repro.serving.FLEET_LANES`):
everything above rides a single typed carry
(:class:`repro.serving.FleetCarry`) whose fields are owned by lane
registrations in ``fleet_lanes.py`` — each lane declares its init, freeze
kind, resume keys, trace/counter/aggregate outputs and telemetry in ONE
place, and all three drivers are thin shells over the same registered scan
body (``_build_fleet_run``).  A disabled lane contributes an *empty*
pytree to the carry, so ``lane=None`` is the lane-less engine by
construction; the streamed driver derives the keys it concatenates/sums
from the registry rather than hand-listing them.  The contract is stated
in docs/RESUME_CONTRACT.md and enforced by ``tests/test_lane_conformance``
+ ``tests/test_resume_contract``.

**Heterogeneous task fleets** (``task=TaskLaneConfig(...)`` or an explicit
``tasks`` (N,) id array): the first lane shipped *through* the registry.
One fleet mixes workloads — HAR wearables and bearing-vibration monitors —
with static per-node task ids that scale the ladder's per-stage energy
costs, select stacked per-task host weights
(:func:`repro.serving.fleet_lanes.stack_task_params`, gathered per node),
and split the psum'd aggregates into ``completed_by_task`` /
``deadline_miss_by_task`` / ``correct_by_task`` (+ ``accuracy_by_task``
when labels are given).  Task ids are static per node, so XLA
constant-folds the switches; ``task=None`` keeps all three engines
bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.aac import AACTable
from ..core.coreset import raw_payload_bytes
from ..core.decision import (D4_SAMPLING, D6_PARTIAL, DEFER,
                             N_INTERMITTENT_DECISIONS, IntermittentConfig)
from ..core.energy import (BrownoutConfig, EnergyCosts, predictor_init,
                           supercap_step)
from ..kernels.ops import signature_corr_op
from ..models.har import HARConfig, quantize_params
from ..obs import (MetricsSpec, categorical_counts, compile_event,
                   int_pair_sum, int_pair_total, metrics_init,
                   metrics_merge, metrics_psum, spec_union)
from ..obs import trace as obs_trace
from ..obs.trace import scope
from ..sharding import make_mesh_compat, node_mesh_axes, shard_map_compat
from .edge_host import (IntermittentState, SeekerNodeState,
                        intermittent_fleet_init, intermittent_lane_step,
                        seeker_host_step, seeker_sensor_step_given_corr)
from .fleet_lanes import (FLEET_LANES, FleetCarry, TaskLaneConfig,
                          fleet_counter_keys, fleet_task_assignment,
                          fleet_telemetry_lanes, fleet_trace_keys,
                          stack_task_params)

__all__ = ["fleet_node_init", "fleet_node_keys", "fleet_telemetry_spec",
           "seeker_fleet_simulate", "seeker_fleet_simulate_sharded",
           "seeker_fleet_simulate_streamed", "wire_bytes_exact"]

N_DECISIONS = DEFER + 1   # D0..D4 + DEFER: bins of the fleet histogram


def _active_lanes(intermittent: IntermittentConfig | None = None,
                  task: TaskLaneConfig | None = None,
                  brownout: BrownoutConfig | None = None) -> frozenset:
    """The engine build's active-lane tag set, from its lane configs.  The
    ``task:K`` tag carries the task count so pure functions of the set (the
    telemetry spec) can size per-task lanes."""
    active = set()
    if brownout is not None:
        active.add("brownout")
    if intermittent is not None:
        active.add("intermittent")
    if task is not None:
        active.update({"task", f"task:{task.n_tasks}"})
    return frozenset(active)


def fleet_telemetry_spec(intermittent: bool = False,
                         n_tasks: int = 0) -> MetricsSpec:
    """The fleet engines' registry lanes (:mod:`repro.obs.registry`),
    DERIVED from the lane registry: each :class:`~repro.serving.fleet_lanes.
    FleetLane` declares the telemetry lanes it owns (node state owns
    ``fleet.wire_bytes``/``fleet.completed``/``fleet.alive_slots``/
    ``fleet.stored_uj``/``fleet.decisions``, brown-out owns
    ``fleet.brownout_*``, the intermittent lane ``fleet.it_*``, the task
    lane ``fleet.task_completed``), and this spec is their
    :func:`repro.obs.spec_union` — spec and carry cannot drift apart.

    Shared by all three engines, so a lane name means the same masked
    quantity everywhere; all lanes are int32 — counter pairs and categorical
    histograms are associative, which is what makes them *bitwise-equal*
    across single-device, sharded and streamed runs (float sums are not
    order-independent and stay out of the parity set)."""
    active = set()
    if intermittent:
        active.add("intermittent")
    if n_tasks:
        active.update({"task", f"task:{n_tasks}"})
    return _fleet_telemetry_spec_cached(frozenset(active))


@functools.lru_cache(maxsize=8)
def _fleet_telemetry_spec_cached(active: frozenset) -> MetricsSpec:
    # memoized on the NORMALIZED lane set, so fleet_telemetry_spec(False)
    # and fleet_telemetry_spec(False, 0) return the identical object — the
    # engines' result["telemetry_spec"] is comparable by `is`
    return spec_union(fleet_telemetry_lanes(active))


def _resolve_telemetry(telemetry,
                       intermittent: IntermittentConfig | None,
                       task: TaskLaneConfig | None = None
                       ) -> MetricsSpec | None:
    """``True`` -> the registry-derived lane set for this build's active
    lanes; a :class:`MetricsSpec` passes through (it must declare the fleet
    lanes); ``None`` stays off."""
    if telemetry is None or telemetry is False:
        return None
    if telemetry is True:
        return fleet_telemetry_spec(intermittent is not None,
                                    task.n_tasks if task is not None else 0)
    if not isinstance(telemetry, MetricsSpec):
        raise TypeError(f"telemetry must be None/True/MetricsSpec, "
                        f"got {type(telemetry).__name__}")
    return telemetry


def _update_fleet_lanes(spec: MetricsSpec, metrics: dict, out_trace: dict,
                        exo_alive_t: jnp.ndarray, active: frozenset,
                        tasks: jnp.ndarray | None = None) -> dict:
    """Advance every registry lane by one slot by folding each registered
    lane's ``telemetry_update`` over the metrics pytree, from the engine's
    MASKED ``out_trace`` quantities — the same post-mask values the
    post-scan aggregates reduce, so carry lanes and aggregates cannot drift
    apart.  Lane updates touch disjoint name-keyed entries, so registration
    order never changes values.  Padding nodes are exogenously dead
    (``alive`` False, ``brownout`` flag frozen False), so they contribute
    to no lane without any extra mask."""
    m = metrics
    for ln in FLEET_LANES:
        if ln.telemetry_update is not None and ln.active(active):
            m = ln.telemetry_update(spec, m, out_trace,
                                    exo_alive_t=exo_alive_t, active=active,
                                    tasks=tasks)
    return m


def fleet_node_init(n_nodes: int, predictor_window: int = 8,
                    initial_uj: float = 50.0) -> SeekerNodeState:
    """Stacked state for ``n_nodes`` nodes (leading node axis on every leaf)."""
    return SeekerNodeState(
        stored_uj=jnp.full((n_nodes,), initial_uj, jnp.float32),
        predictor=predictor_init(predictor_window, batch=n_nodes),
        prev_label=jnp.zeros((n_nodes,), jnp.int32))


def fleet_node_keys(key: jax.Array, n_nodes: int) -> jnp.ndarray:
    """The PRNG lane's init: node ``i``'s stream is ``fold_in(key, i)``, so
    a fleet of N nodes is bit-compatible with N independent single-node
    runs (and with any shard layout of the same fleet)."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n_nodes))


def _make_fleet_step(har_cfg: HARConfig, costs: EnergyCosts, quant_bits: int,
                     k_max: int, m_samples: int, corr_threshold: float,
                     shared_stream: bool, t: int, node_block: int | None,
                     brownout: BrownoutConfig | None,
                     intermittent: IntermittentConfig | None = None,
                     telemetry: MetricsSpec | None = None,
                     task: TaskLaneConfig | None = None,
                     coresets: bool = False):
    """One fleet time slot, shared VERBATIM by the single-device scan and the
    per-shard scan inside ``shard_map`` — the sharded engine sees exactly this
    computation on its local node tile.

    ``node_block``: XLA lowers matmuls/convs differently for different batch
    shapes, so a node's float results can drift ~1e-7 between a (N,) batch
    and a (N/d,) shard tile.  With ``node_block`` set, the per-slot fleet
    math runs as a ``lax.map`` over fixed-(node_block,) microbatches — the
    mapped body is compiled ONCE at a batch shape independent of fleet size
    or shard layout, so sharded and unsharded runs are bit-identical.
    ``None`` keeps the one-shot full-batch vmap (fastest; bitwise only for
    integer/energy traces across layouts).

    ``intermittent``: the partial-inference lane.  When set, the scan carry
    gains a stacked :class:`repro.serving.edge_host.IntermittentState` and
    the per-slot inputs gain the global slot index; the lane runs INSIDE
    ``block_body`` so its conv/matmul stages see the same microbatch shapes
    as the ladder (bitwise across shard layouts under a common
    ``node_block``).  The lane's state obeys the same ``keep()`` freeze as
    the rest of the carry — a browned-out or dead node's suspended
    activations survive untouched until it rejoins, which is exactly the
    suspend-across-brown-out semantics.

    ``coresets``: also emit each node's cluster coreset (after the AAC
    mask) and sampling coreset as the slot computed them, under the trace
    key ``"coresets"`` — the payloads a host tier takes in.  ``False``
    keeps the step's program unchanged."""

    strict = brownout is not None or intermittent is not None

    def block_body(state, keys, it, tasks_b, win_t, harv_t, slot, signatures,
                   qdnn_params, host_params, gen_params, aac_table,
                   aux_params):
        # same split discipline as the single-node scan:
        # carry, sensor, host
        ks = jax.vmap(lambda kk: jax.random.split(kk, 3))(keys)  # (B,3,2)

        # memoization hot path: one batched signature-bank correlation for
        # the whole (local) fleet — under shard_map this is the (N/d, L)
        # tile, so the Pallas/ref kernel runs per-shard with no collectives
        with scope("seeker.corr"):
            corr = signature_corr_op(win_t, signatures)   # (B, L)

        if task is None:
            out = jax.vmap(
                lambda w, st, h, co, kk: seeker_sensor_step_given_corr(
                    w, st, h, co, qdnn_params=qdnn_params, har_cfg=har_cfg,
                    aac_table=aac_table, costs=costs, key=kk, k_max=k_max,
                    m_samples=m_samples, quant_bits=quant_bits,
                    corr_threshold=corr_threshold, strict_energy=strict)
            )(win_t, state, harv_t, corr, ks[:, 1])
        else:
            # task lane: each node's WHOLE cost ladder scales by its task's
            # declared factor — a separate vmap variant so ``task=None``
            # keeps the exact pre-lane jaxpr
            scale = jnp.asarray(task.cost_scale, jnp.float32)[tasks_b]
            out = jax.vmap(
                lambda w, st, h, co, kk, cs: seeker_sensor_step_given_corr(
                    w, st, h, co, qdnn_params=qdnn_params, har_cfg=har_cfg,
                    aac_table=aac_table, costs=costs, key=kk, k_max=k_max,
                    m_samples=m_samples, quant_bits=quant_bits,
                    corr_threshold=corr_threshold, strict_energy=strict,
                    cost_scale=cs)
            )(win_t, state, harv_t, corr, ks[:, 1], scale)
        if intermittent is not None:
            # the lane overrides engaged slots AFTER the ladder: in-flight
            # inferences resume before new work, DEFER slots become staged
            # progress / early exits.  Quantize the backbone once per slot.
            qp = quantize_params(qdnn_params, quant_bits)
            reserve = brownout.off_uj if brownout is not None else 0.0
            if task is None:
                lane = jax.vmap(
                    lambda w, st, h, dec, itn: intermittent_lane_step(
                        w, st, h, dec, itn, slot, qp=qp,
                        aux_params=aux_params, har_cfg=har_cfg, costs=costs,
                        quant_bits=quant_bits, cfg=intermittent,
                        reserve_uj=reserve)
                )(win_t, state, harv_t, out.decision, it)
            else:
                lane = jax.vmap(
                    lambda w, st, h, dec, itn, cs: intermittent_lane_step(
                        w, st, h, dec, itn, slot, qp=qp,
                        aux_params=aux_params, har_cfg=har_cfg, costs=costs,
                        quant_bits=quant_bits, cfg=intermittent,
                        reserve_uj=reserve, cost_scale=cs)
                )(win_t, state, harv_t, out.decision, it, scale)
            eng = lane.engaged
            lane_state = SeekerNodeState(
                stored_uj=jnp.where(eng, lane.stored_uj,
                                    out.state.stored_uj),
                predictor=out.state.predictor,
                prev_label=jnp.where(eng, lane.prev_label,
                                     out.state.prev_label))
            # label_or_neg = -1 on engaged slots: the host's one_hot(-1)
            # contributes zeros, so D6/D7/D8 slots put nothing into the
            # slot-aligned ensemble (the emitted result belongs to the
            # SOURCE slot; it is scored through the it_* traces instead)
            out = out._replace(
                decision=jnp.where(eng, lane.decision, out.decision),
                payload_bytes=jnp.where(eng, lane.payload_bytes,
                                        out.payload_bytes),
                label_or_neg=jnp.where(eng, -1, out.label_or_neg),
                state=lane_state)
            new_it = lane.state
        else:
            new_it = None
        if task is not None and task.per_task_host:
            # kind-switched host recovery/DNN: host_params arrives STACKED
            # on a leading task axis (stack_task_params); each node's host
            # step gathers its task's tree inside the vmap, so the compiled
            # shapes stay task-independent
            host_logits = jax.vmap(
                lambda o, kk, tid: seeker_host_step(
                    o, host_params=jax.tree_util.tree_map(
                        lambda p: p[tid], host_params),
                    gen_params=gen_params, har_cfg=har_cfg, key=kk, t=t)
            )(out, ks[:, 2], tasks_b)
        else:
            host_logits = jax.vmap(
                lambda o, kk: seeker_host_step(
                    o, host_params=host_params, gen_params=gen_params,
                    har_cfg=har_cfg, key=kk, t=t)
            )(out, ks[:, 2])
        trace = {"decision": out.decision, "payload": out.payload_bytes,
                 "stored": out.state.stored_uj, "k": out.coreset_k,
                 "logits": host_logits}
        if intermittent is not None:
            trace.update({"it_emit": lane.emit, "it_label": lane.emit_label,
                          "it_conf": lane.emit_conf, "it_src": lane.emit_src,
                          "it_stage": lane.emit_stage})
        if coresets:
            trace["coresets"] = {
                "centers": out.coreset_centers, "radii": out.coreset_radii,
                "counts": out.coreset_counts, "samp_idx": out.samp_idx,
                "samp_vals": out.samp_vals, "samp_mean": out.samp_mean,
                "samp_var": out.samp_var}
        return out.state, ks[:, 0], new_it, trace

    active = _active_lanes(intermittent, task, brownout)

    def step(carry, inp, tasks, signatures, qdnn_params, host_params,
             gen_params, aac_table, aux_params=None):
        # the typed carry: one field per registered lane, None for absent
        # lanes (an empty pytree — no scan slots, no ops), which is what
        # keeps ``lane=None`` engines bitwise-identical to engines built
        # before the lane existed.  The telemetry field is the fleet-level
        # accumulator lane — never passed through keep(): it holds masked
        # counts, not per-node state.
        state, keys, browned, it, metrics = carry
        win_t, harv_t, alive_t, slot = inp
        n = keys.shape[0]
        # the per-slot alive lane: the exogenous trace composed with the
        # endogenous brown-out flag carried through the scan — a node runs
        # only when its trace says so AND its supercap hysteresis allows
        with scope("seeker.lanes"):
            alive_eff = ((alive_t & ~browned) if brownout is not None
                         else alive_t)
        if shared_stream:
            win_t = jnp.broadcast_to(win_t[None], (n,) + win_t.shape)

        if node_block is None or node_block == n:
            new_state, new_keys, new_it, trace = block_body(
                state, keys, it, tasks, win_t, harv_t, slot, signatures,
                qdnn_params, host_params, gen_params, aac_table, aux_params)
        else:
            # fixed-shape microbatches: pad the node axis to the block
            # quantum (rows are independent, padding is sliced off) and map
            # the identical compiled body over groups — a shard tile SMALLER
            # than the block pads up to it, so every layout runs batch-
            # (node_block,) bodies
            pad = (-n) % node_block
            grp = (n + pad) // node_block

            def regroup(x):
                x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
                return x.reshape((grp, node_block) + x.shape[1:])

            def ungroup(x):
                return x.reshape((grp * node_block,) + x.shape[2:])[:n]

            st_g, ks_g, it_g, tk_g, w_g, h_g = jax.tree_util.tree_map(
                regroup, (state, keys, it, tasks, win_t, harv_t))
            new_state, new_keys, new_it, trace = jax.tree_util.tree_map(
                ungroup,
                jax.lax.map(
                    lambda a: block_body(a[0], a[1], a[2], a[3], a[4], a[5],
                                         slot, signatures, qdnn_params,
                                         host_params, gen_params, aac_table,
                                         aux_params),
                    (st_g, ks_g, it_g, tk_g, w_g, h_g)))

        with scope("seeker.lanes"):
            # --- churn lane: a dead node harvests nothing, freezes its
            # whole carry (charge, predictor, AAC continuity AND its PRNG
            # stream — on rejoin it continues exactly where it browned
            # out), and emits DEFER with zero payload.  With an all-True
            # trace every select picks the freshly-computed value, so the
            # churn-free run is bitwise unchanged.
            def keep(new, old):
                a = alive_eff.reshape((n,) + (1,) * (new.ndim - 1))
                return jnp.where(a, new, old)

            new_state = jax.tree_util.tree_map(keep, new_state, state)
            new_keys = keep(new_keys, keys)
            if intermittent is not None:
                # suspended staged activations freeze through dead AND browned-
                # out slots like every other carry lane — suspend-across-
                # brown-out falls out of the same select
                new_it = jax.tree_util.tree_map(keep, new_it, it)
            if brownout is not None:
                # --- endogenous brown-out: the MCU is down but the harvester
                # keeps trickle-charging the supercap, so a browned-out (yet
                # exogenously-present) node's charge still integrates income;
                # an exogenously-dead node stays fully frozen (PR-4 lane).
                trickle = supercap_step(state.stored_uj, harv_t, 0.0)
                stored = jnp.where(alive_eff, new_state.stored_uj,
                                   jnp.where(alive_t, trickle,
                                             state.stored_uj))
                new_state = new_state._replace(stored_uj=stored)
                # hysteresis on the POST-slot charge: running nodes brown
                # out below off_uj, browned-out nodes rejoin at restart_uj;
                # the flag freezes (like everything else) through
                # exogenously-dead slots
                next_browned = jnp.where(browned, stored < brownout.restart_uj,
                                         stored < brownout.off_uj)
                next_browned = jnp.where(alive_t, next_browned, browned)
            else:
                next_browned = browned
            out_trace = {
                "decision": jnp.where(alive_eff, trace["decision"], DEFER),
                "payload": jnp.where(alive_eff, trace["payload"], 0.0),
                "stored": new_state.stored_uj,
                "k": jnp.where(alive_eff, trace["k"], 0),
                "logits": jnp.where(alive_eff[:, None], trace["logits"], 0.0),
                "alive": alive_eff,          # exogenous ∧ ¬browned_out
                "brownout": browned,         # the flag entering the slot
                "bo_event": next_browned & ~browned,   # brown-out onsets
            }
            if intermittent is not None:
                # a dead/browned-out node ran no lane this slot: its emission
                # lane is masked like the decision lane (the label/conf/src
                # fields are only meaningful where it_emit > 0)
                out_trace.update({
                    "it_emit": jnp.where(alive_eff, trace["it_emit"], 0),
                    "it_label": trace["it_label"],
                    "it_conf": trace["it_conf"],
                    "it_src": trace["it_src"],
                    "it_stage": trace["it_stage"],
                })
            if coresets:
                out_trace["coresets"] = trace["coresets"]
            new_metrics = (None if telemetry is None else _update_fleet_lanes(
                telemetry, metrics, out_trace, alive_t, active, tasks))
        return FleetCarry(node=new_state, keys=new_keys,
                          brownout=next_browned, intermittent=new_it,
                          telemetry=new_metrics), out_trace

    return step


@functools.lru_cache(maxsize=32)
def _build_fleet_run(har_cfg: HARConfig, costs: EnergyCosts, quant_bits: int,
                     k_max: int, m_samples: int, corr_threshold: float,
                     shared_stream: bool, node_block: int | None,
                     brownout: BrownoutConfig | None, donate: bool,
                     intermittent: IntermittentConfig | None = None,
                     telemetry: MetricsSpec | None = None,
                     task: TaskLaneConfig | None = None,
                     coresets: bool = False):
    """Compile-cached fleet scan, keyed on the static configuration.

    All arrays (params, signatures, windows, state) are jit *arguments*, so
    repeated simulations with the same config — the benchmark's timed
    iterations, a serving loop — reuse the compiled executable instead of
    re-tracing a fresh closure each call.

    ONE signature for every lane combination: absent lanes pass ``None``
    (an empty pytree contributing no jit inputs and no scan slots), so
    ``lane=None`` stays bitwise-off without per-combination run variants —
    the scan body is the same registered :class:`FleetCarry` step for every
    driver.  ``xs_slots`` is always an input (the intermittent lane's
    global slot indices; unused — and dead-code-eliminated — without the
    lane).  With ``telemetry`` the carry's telemetry field starts from
    ZERO — the run computes a telemetry *delta*, merged with any resumed
    ``telemetry_state0`` host-side, which is what keeps the sharded engine
    from double-counting a replicated carry-in on psum.
    """

    def run(state0, keys0, browned0, it0, tasks, xs_w, xs_h, xs_alive,
            xs_slots, signatures, qdnn_params, host_params, gen_params,
            aac_table, aux_params):
        compile_event("fleet.run")
        obs_trace.instant("compile:fleet.run")
        t = xs_w.shape[-2]
        step = _make_fleet_step(har_cfg, costs, quant_bits, k_max,
                                m_samples, corr_threshold, shared_stream,
                                t, node_block, brownout, intermittent,
                                telemetry=telemetry, task=task,
                                coresets=coresets)
        carry0 = FleetCarry(
            node=state0, keys=keys0, brownout=browned0, intermittent=it0,
            telemetry=None if telemetry is None else metrics_init(telemetry))
        final, traces = jax.lax.scan(
            lambda c, i: step(c, i, tasks, signatures, qdnn_params,
                              host_params, gen_params, aac_table,
                              aux_params),
            carry0, (xs_w, xs_h, xs_alive, xs_slots))
        # the final carry IS the resume contract: a resumed run
        # (state0=final_state, node_keys=final_keys,
        # brownout_state0=final_brownout,
        # intermittent_state0=final_intermittent, slot0=slots run so far,
        # telemetry_state0=res["telemetry"]) continues each lane exactly
        # where it stopped instead of replaying segment 1
        return traces, final

    # donate the stacked node state (it is returned, so XLA can alias it)
    return jax.jit(run, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=32)
def _build_fleet_run_sharded(mesh, axis_names: tuple[str, ...],
                             har_cfg: HARConfig, costs: EnergyCosts,
                             quant_bits: int, k_max: int, m_samples: int,
                             corr_threshold: float, shared_stream: bool,
                             per_node_labels: bool,
                             node_block: int | None,
                             brownout: BrownoutConfig | None, donate: bool,
                             intermittent: IntermittentConfig | None = None,
                             telemetry: MetricsSpec | None = None,
                             task: TaskLaneConfig | None = None):
    """Compile-cached SHARDED fleet scan: the whole time scan runs inside the
    ``shard_map`` manual region, each shard scanning its local node tile
    with the SAME registered :class:`FleetCarry` step as the single-device
    driver; only the masked fleet aggregates (and, with ``telemetry``, the
    registry lanes via :func:`repro.obs.metrics_psum`) are ``psum``-ed over
    ``axis_names``.

    Like :func:`_build_fleet_run`, ONE signature covers every lane
    combination — absent lanes pass ``None``, whose shard specs broadcast
    over zero leaves.  ``per_node_labels`` switches the accuracy aggregate
    between one shared (S,) label track (replicated) and per-node (S, N)
    tracks (sharded over the node axes like every other per-node array);
    the task lane's (N,) ids shard over the node axes and its per-task
    splits join the psum'd aggregate set."""
    nodes = P(axis_names)                    # leading node dim over the mesh
    time_nodes = P(None, axis_names)         # (S, N, ...) time-major traces
    repl = P()                               # replicated (params, bank, mask)

    def shard_body(state0, keys0, browned0, it0, tasks, xs_w, xs_h,
                   xs_alive, xs_slots, mask, labels, signatures,
                   qdnn_params, host_params, gen_params, aac_table,
                   aux_params):
        compile_event("fleet.run_sharded")
        obs_trace.instant("compile:fleet.run_sharded")
        t = xs_w.shape[-2]
        step = _make_fleet_step(har_cfg, costs, quant_bits, k_max,
                                m_samples, corr_threshold, shared_stream,
                                t, node_block, brownout, intermittent,
                                telemetry=telemetry, task=task)
        carry0 = FleetCarry(
            node=state0, keys=keys0, brownout=browned0, intermittent=it0,
            telemetry=None if telemetry is None else metrics_init(telemetry))
        final, traces = jax.lax.scan(
            lambda c, i: step(c, i, tasks, signatures, qdnn_params,
                              host_params, gen_params, aac_table,
                              aux_params),
            carry0, (xs_w, xs_h, xs_alive, xs_slots))

        def psum(x):
            with scope("seeker.collectives"):
                return jax.lax.psum(x, axis_names)

        aggs = _fleet_aggregates(
            traces, xs_alive, labels, per_node_labels, intermittent,
            xs_slots[0] if intermittent is not None else 0,
            tasks=tasks, task=task, mask=mask, reduce=psum)
        # registry lanes are summed per shard then psum'd component-wise;
        # the psum'd delta is replicated (out-spec P() per lane)
        if telemetry is not None:
            with scope("seeker.collectives"):
                final = final._replace(telemetry=metrics_psum(
                    telemetry, final.telemetry, axis_names))
        return traces, final, aggs

    in_specs = (nodes, nodes, nodes,   # state0 (pytree), keys0, browned0
                nodes,                            # it0 (lane state | None)
                nodes,                            # tasks (N,) | None
                repl if shared_stream else time_nodes,   # xs_w
                time_nodes,                       # xs_h (S, N)
                time_nodes,                       # xs_alive (S, N)
                repl,                             # xs_slots (S,)
                nodes,                            # mask (N,)
                time_nodes if per_node_labels else repl,  # labels
                repl, repl, repl, repl, repl, repl)
    out_specs = (time_nodes,                      # traces
                 FleetCarry(node=nodes, keys=nodes, brownout=nodes,
                            intermittent=nodes, telemetry=repl),
                 repl)                            # psum'd aggregates

    fn = shard_map_compat(
        shard_body, mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset(axis_names))
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def _stack_pad_state(state0: SeekerNodeState | None, n: int, pad: int,
                     predictor_window: int, initial_uj: float
                     ) -> SeekerNodeState:
    """Resolve the fleet's initial state: a caller-provided stacked state
    (serving loops resuming a fleet keep their supercapacitor charge) or a
    fresh init, extended with ``pad`` inert default-init rows."""
    if state0 is None:
        return fleet_node_init(n + pad, predictor_window, initial_uj)
    lead = jax.tree_util.tree_leaves(state0)[0].shape[0]
    if lead != n:
        raise ValueError(f"state0 is stacked for {lead} nodes, fleet has {n}")
    if pad == 0:
        return state0
    filler = fleet_node_init(pad, predictor_window, initial_uj)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=0), state0, filler)


def _resolve_labels(labels, s: int, n: int, shared_stream: bool
                    ) -> tuple[jnp.ndarray | None, bool]:
    """Validate the ``labels`` argument against the stream layout.

    Returns ``(labels, per_node)``: a shared (S,) track (only meaningful
    when every node sees the same stream) or per-node (S, N) tracks.  A
    shared track with per-node streams is REJECTED — scoring N different
    window streams against one label track is exactly the silent accuracy
    bug this check exists to stop.
    """
    if labels is None:
        return None, False
    labels = jnp.asarray(labels)
    accepted = (f"accepted forms: (S,)=({s},) shared-stream track, or "
                f"(S, N)=({s}, {n}) per-node tracks (padded/sharded like "
                f"harvest; mixed-task fleets score each node's track "
                f"against its own task)")
    if labels.shape == (s, n):
        return labels.astype(jnp.int32), True
    if labels.shape == (s,):
        if not shared_stream and n != 1:
            raise ValueError(
                f"labels shape {labels.shape} is ambiguous with per-node "
                f"(N, S, T, C) window streams: each of the {n} nodes plays "
                f"its own stream, so accuracy against one shared "
                f"(S,)=({s},) label track is meaningless.  Pass per-node "
                f"(S, N)=({s}, {n}) labels or a shared (S, T, C) window "
                f"stream; {accepted}.")
        return labels.astype(jnp.int32), False
    raise ValueError(
        f"labels must be one of the accepted forms, got shape "
        f"{labels.shape}; {accepted}.")


def _resolve_alive(alive, n: int, s: int) -> jnp.ndarray:
    """(N, S) bool churn trace; ``None`` = the always-registered fleet."""
    if alive is None:
        return jnp.ones((n, s), bool)
    alive = jnp.asarray(alive)
    if alive.shape != (n, s):
        raise ValueError(f"alive must be (N, S)=({n}, {s}) bool, "
                         f"got {alive.shape}")
    return alive.astype(bool)


def _wire_byte_pair(payload: jnp.ndarray, act: jnp.ndarray) -> jnp.ndarray:
    """Exact integer bytes-on-wire as a (2,) int32 ``[hi, lo]`` pair with
    value ``hi * 2**16 + lo``.

    Payloads are integral whole-byte counts (``aac_bytes``,
    ``sampling_payload_bytes``), but the legacy ``bytes_on_wire`` float32
    sum loses exactness past 2**24 at fleet scale.  int64 is unavailable
    with jax's default x64-off config, so the reduction goes hierarchical:
    per-node slot totals stay exact in int32 (payload < 2**16 B per slot,
    so any S < 2**31 / 2**16 ≈ 32k-slot-of-max-payload per node — in
    practice S < ~3M slots at the 720-B raw bound), then the node reduction
    splits each total into base-2**16 digits whose int32 sums (and psums)
    stay exact to N < 32768 nodes.  The pair is not normalized (``lo`` may
    exceed 2**16); combine with :func:`wire_bytes_exact`.
    """
    p = jnp.where(act, jnp.round(payload).astype(jnp.int32), 0)
    # hierarchical: per-node totals stay exact in int32, then the digit
    # split + reduction is the registry's shared primitive
    return int_pair_sum(jnp.sum(p, axis=0))               # (N,) -> (2,)


def wire_bytes_exact(res: dict) -> int:
    """Combine an engine result's ``bytes_on_wire_i32`` pair into the exact
    total bytes the fleet put on the wire, as an arbitrary-precision Python
    int (the float32 ``bytes_on_wire`` is kept for compatibility but is
    only approximate past 2**24)."""
    return int_pair_total(res["bytes_on_wire_i32"])


def _resolve_brownout0(brownout_state0, state0: SeekerNodeState,
                       brownout: BrownoutConfig | None, n: int
                       ) -> jnp.ndarray:
    """(N,) bool brown-out flag entering slot 0: an explicitly resumed flag
    (a previous run's ``final_brownout``), else boot-time hysteresis (a node
    whose initial charge is already under ``off_uj`` boots browned out),
    else the inert all-False lane when brown-out is disabled."""
    if brownout_state0 is not None:
        browned0 = jnp.asarray(brownout_state0)
        if browned0.shape != (n,):
            raise ValueError(f"brownout_state0 must be (N,)=({n},) bool, "
                             f"got {browned0.shape}")
        return browned0.astype(bool)
    if brownout is not None:
        return state0.stored_uj[:n] < brownout.off_uj
    return jnp.zeros((n,), bool)


def _validate_intermittent_args(intermittent, intermittent_state0,
                                aux_params, n: int) -> None:
    """Reject half-configured intermittent runs before tracing: the lane
    needs its auxiliary heads, and a resumed lane state without the lane
    enabled would silently be ignored."""
    if intermittent is None:
        if intermittent_state0 is not None:
            raise ValueError(
                "intermittent_state0 was passed but intermittent is None — "
                "a resumed lane state without the lane enabled would be "
                "silently dropped; pass the IntermittentConfig too")
        return
    if aux_params is None:
        raise ValueError(
            "intermittent inference needs the early-exit auxiliary heads: "
            "pass aux_params=har_aux_init(key, har_cfg)")
    if intermittent_state0 is not None:
        lead = intermittent_state0.stage.shape[0]
        if lead != n:
            raise ValueError(
                f"intermittent_state0 is stacked for {lead} nodes, "
                f"fleet has {n}")


def _resolve_tasks(tasks, task: TaskLaneConfig | None, n: int
                   ) -> tuple[jnp.ndarray | None, TaskLaneConfig | None]:
    """Resolve the heterogeneous-task lane's per-node ids + config.

    ``task`` alone defaults to the round-robin
    :func:`repro.serving.fleet_lanes.fleet_task_assignment`; ``tasks``
    alone gets the default two-task :class:`TaskLaneConfig`.  Ids are
    validated against the config's task count host-side (they are static
    per-node run arguments, not traced)."""
    if tasks is None and task is None:
        return None, None
    if task is None:
        task = TaskLaneConfig()
    if tasks is None:
        tasks = fleet_task_assignment(n, task.n_tasks)
    tasks = jnp.asarray(tasks, jnp.int32)
    if tasks.shape != (n,):
        raise ValueError(
            f"tasks must be (N,)=({n},) per-node task ids, "
            f"got {tasks.shape}")
    lo, hi = int(jnp.min(tasks)), int(jnp.max(tasks))
    if lo < 0 or hi >= task.n_tasks:
        raise ValueError(
            f"tasks ids span [{lo}, {hi}] but the TaskLaneConfig declares "
            f"{task.n_tasks} tasks {task.names}")
    return tasks, task


def _resolve_task_host(task: TaskLaneConfig | None, host_params):
    """With ``per_task_host``, ``host_params`` must arrive as one tree per
    task; stack them leaf-wise so each node's host step can gather its
    task's tree at fixed shapes (:func:`stack_task_params`)."""
    if task is None or not task.per_task_host:
        return host_params
    if not isinstance(host_params, (tuple, list)):
        raise ValueError(
            f"per_task_host=True needs host_params as a sequence of "
            f"{task.n_tasks} per-task param trees "
            f"(one per {task.names}), got {type(host_params).__name__}")
    if len(host_params) != task.n_tasks:
        raise ValueError(
            f"per_task_host=True needs {task.n_tasks} host param trees "
            f"for tasks {task.names}, got {len(host_params)}")
    return stack_task_params(host_params)


def _fleet_aggregates(traces: dict, exo_alive: jnp.ndarray,
                      labels: jnp.ndarray | None, per_node: bool,
                      intermittent: IntermittentConfig | None = None,
                      slot0=0, tasks: jnp.ndarray | None = None,
                      task: TaskLaneConfig | None = None,
                      mask: jnp.ndarray | None = None,
                      reduce=None) -> dict:
    """Masked fleet aggregates from (S, N) traces — ONE function for both
    engines: the single-device driver calls it host-side after the run
    (``mask=None``, identity ``reduce``); the sharded engine calls it
    inside the shard_map region on its local tile, with the static padding
    ``mask`` composed into the activity mask and ``reduce`` wrapping every
    aggregate in a ``psum`` — int counters are exactly equal across engines
    because every reduction here is an associative integer sum (tests
    cross-check them).  The activity mask is the engine's EMITTED alive
    lane (exogenous ∧ ¬browned_out); ``exo_alive`` is the exogenous trace
    alone, needed to count the slots the brown-out hysteresis suppressed.

    With ``intermittent`` the completion aggregate excludes D6 (a suspended
    inference put nothing on the wire), the histogram grows to the 9-code
    ladder, and emission counters + source-slot-scored accuracy splits are
    added; ``slot0`` is the absolute slot index of this run's first slot —
    emissions whose ``it_src`` predates it (a resumed segment finishing an
    earlier segment's inference) are masked out of the accuracy counters
    here and rescored by the streamed driver over the concatenated traces.

    With the task lane (``tasks``/``task``) every completion/miss/accuracy
    count additionally splits per task id via
    :func:`repro.obs.categorical_counts` — integer histograms over the
    broadcast (S, N) task ids, so the splits psum exactly like the totals:
    ``completed_by_task``, ``deadline_miss_by_task`` (an alive slot that
    put no result on the wire missed its slot deadline) and, with labels,
    ``correct_by_task``."""
    red = reduce if reduce is not None else (lambda x: x)
    act = traces["alive"]
    if mask is not None:
        act = act & mask[None, :]
    if intermittent is None:
        sent = (traces["decision"] != DEFER) & act
        n_bins = N_DECISIONS
    else:
        # D6 suspends with nothing on the wire; D7/D8 are completions
        sent = ((traces["decision"] != DEFER)
                & (traces["decision"] != D6_PARTIAL) & act)
        n_bins = N_INTERMITTENT_DECISIONS
    bo = traces["brownout"] & exo_alive
    bo_event = traces["bo_event"]
    if mask is not None:
        # padding nodes are exogenously dead: they never brown "in" and
        # contribute to neither brown-out count
        bo = bo & mask[None, :]
        bo_event = bo_event & mask[None, :]
    aggs = {
        "bytes_on_wire": red(
            jnp.sum(jnp.where(act, traces["payload"], 0.0))),
        "bytes_on_wire_i32": red(_wire_byte_pair(traces["payload"], act)),
        "decision_histogram": red(categorical_counts(
            traces["decision"], n_bins, act)),
        "completed": red(jnp.sum(sent.astype(jnp.int32))),
        "alive_slots": red(jnp.sum(act.astype(jnp.int32))),
        "brownout_slots": red(jnp.sum(bo.astype(jnp.int32))),
        "brownout_events": red(jnp.sum(bo_event.astype(jnp.int32))),
    }
    if intermittent is not None:
        emit = traces["it_emit"]
        aggs["it_full"] = red(
            jnp.sum(((emit == 2) & act).astype(jnp.int32)))
        aggs["it_early"] = red(
            jnp.sum(((emit == 1) & act).astype(jnp.int32)))
    tasks_b = (None if tasks is None else
               jnp.broadcast_to(tasks[None, :], act.shape))
    if task is not None:
        aggs["completed_by_task"] = red(
            categorical_counts(tasks_b, task.n_tasks, sent))
        aggs["deadline_miss_by_task"] = red(
            categorical_counts(tasks_b, task.n_tasks, act & ~sent))
    if labels is None:
        return aggs
    preds = jnp.argmax(traces["logits"], axis=-1)
    # per-node labels arrive as (S, N) tracks (under shard_map: the shard's
    # own tile); a shared track broadcasts over the node axis
    ok = (preds == labels) if per_node else (preds == labels[:, None])
    if intermittent is None:
        aggs["correct"] = red(jnp.sum((ok & sent).astype(jnp.int32)))
        if task is not None:
            aggs["correct_by_task"] = red(
                categorical_counts(tasks_b, task.n_tasks, ok & sent))
        return aggs
    # ladder accuracy scores the slot-aligned host logits; lane emissions
    # score against the label of their SOURCE slot (the staged window's
    # capture slot, gathered through it_src)
    ladder_sent = sent & (traces["decision"] <= D4_SAMPLING)
    s = traces["decision"].shape[0]
    rel = traces["it_src"] - slot0
    valid = (traces["it_emit"] > 0) & act & (rel >= 0)
    rel_c = jnp.clip(rel, 0, s - 1)
    lab = (jnp.take_along_axis(labels, rel_c, axis=0) if per_node
           else labels[rel_c])
    it_ok = (traces["it_label"] == lab) & valid
    aggs["correct_ladder"] = red(
        jnp.sum((ok & ladder_sent).astype(jnp.int32)))
    aggs["it_correct_full"] = red(
        jnp.sum((it_ok & (traces["it_emit"] == 2)).astype(jnp.int32)))
    aggs["it_correct_early"] = red(
        jnp.sum((it_ok & (traces["it_emit"] == 1)).astype(jnp.int32)))
    aggs["correct"] = (aggs["correct_ladder"] + aggs["it_correct_full"]
                       + aggs["it_correct_early"])
    if task is not None:
        aggs["correct_by_task"] = red(
            categorical_counts(tasks_b, task.n_tasks, ok & ladder_sent)
            + categorical_counts(tasks_b, task.n_tasks,
                                 it_ok & (traces["it_emit"] == 2))
            + categorical_counts(tasks_b, task.n_tasks,
                                 it_ok & (traces["it_emit"] == 1)))
    return aggs


def seeker_fleet_simulate(windows: jnp.ndarray, harvest: jnp.ndarray, *,
                          signatures, qdnn_params, host_params, gen_params,
                          har_cfg: HARConfig,
                          aac_table: AACTable | None = None,
                          costs: EnergyCosts | None = None,
                          key: jax.Array | None = None, quant_bits: int = 16,
                          k_max: int = 12, m_samples: int = 20,
                          corr_threshold: float = 0.95,
                          predictor_window: int = 8, initial_uj: float = 50.0,
                          state0: SeekerNodeState | None = None,
                          node_keys: jax.Array | None = None,
                          labels: jnp.ndarray | None = None,
                          alive: jnp.ndarray | None = None,
                          brownout: BrownoutConfig | None = None,
                          brownout_state0: jnp.ndarray | None = None,
                          node_block: int | None = None,
                          donate: bool = True,
                          intermittent: IntermittentConfig | None = None,
                          intermittent_state0: IntermittentState | None = None,
                          aux_params: dict | None = None,
                          slot0: int = 0,
                          telemetry=None,
                          telemetry_state0: dict | None = None,
                          tasks: jnp.ndarray | None = None,
                          task: TaskLaneConfig | None = None,
                          coresets: bool = False):
    """Simulate N independent Seeker nodes over S time slots in one scan.

    Args:
        windows: (S, T, C) — one stream shared by every node (the sensor-
            ensemble deployment), or (N, S, T, C) — a stream per node.
        harvest: (N, S) µJ harvested per node per slot (heterogeneous traces;
            see :func:`repro.core.energy.fleet_harvest_traces`).
        key: fleet PRNG; node ``i`` uses ``fold_in(key, i)`` and then splits
            exactly like the single-node simulator, so an N=1 fleet
            reproduces a single-node run.
        state0: optional stacked ``SeekerNodeState`` to resume from (e.g. the
            ``final_state`` of a previous run) — supercapacitor charge,
            predictor history and AAC continuity carry over instead of being
            silently reset to ``initial_uj``.  NOTE: with ``donate=True`` the
            passed state's buffers are donated to the run.
        node_keys: optional (N, 2) per-node PRNG keys to resume from (a
            previous run's ``final_keys``) — without them a resumed segment
            re-derives ``fold_in(key, i)`` and replays segment 1's random
            draws.  ``state0 + node_keys`` makes a chain of runs bitwise
            equal to one long run.
        labels: optional ground truth for the ``fleet_accuracy`` aggregate:
            (S,) for a shared stream, or per-node (S, N) tracks.  A shared
            (S,) track with per-node window streams raises — see
            :func:`_resolve_labels`.
        alive: optional (N, S) bool churn trace
            (:func:`repro.core.energy.fleet_alive_traces`) — dead slots
            freeze the node (state AND PRNG stream), emit DEFER with zero
            payload, and drop out of every aggregate.  An all-True trace is
            bitwise-identical to ``None``.
        brownout: optional :class:`repro.core.energy.BrownoutConfig` —
            ENDOGENOUS churn: the decision ladder switches to strict
            store-and-execute affordability (spend ≤ stored + harvested this
            slot; the forecast ranks but no longer mints energy), and the
            per-slot alive lane becomes ``alive_trace ∧ ¬browned_out`` with
            ``browned_out`` carried through the scan and flipped by the
            supercap hysteresis (below ``off_uj`` the node powers down and
            trickle-charges; at ``restart_uj`` it reboots into its frozen
            state).  ``None`` keeps today's engine bitwise.
        brownout_state0: optional (N,) bool — resume the brown-out flag from
            a previous run's ``final_brownout`` (the streamed driver does);
            default is boot-time hysteresis on the initial charge.
        node_block: run per-slot fleet math in fixed-size node microbatches
            (see :func:`_make_fleet_step`) — results become bit-identical
            across fleet sizes and shard layouts that use the same block.
            ``None`` (default) is the fastest full-batch path.
        donate: donate the stacked node state to the jitted run so XLA can
            alias its buffers into the returned final state (the key array
            has no matching output and is never donated).
        intermittent: optional :class:`repro.core.decision.IntermittentConfig`
            — enables the staged intermittent-inference lane: slots the
            ladder would DEFER instead advance a staged quantized inference
            as far as this slot's strict ``stored + harvested`` budget
            affords, suspending the activations in the scan carry across
            slots and brown-outs (see docs/ENERGY_MODEL.md).  Requires
            ``aux_params`` (:func:`repro.models.har.har_aux_init`).  ``None``
            keeps the engine bitwise-identical to the legacy path.
        intermittent_state0: optional stacked
            :class:`repro.serving.edge_host.IntermittentState` to resume a
            suspended fleet from (a previous run's ``final_intermittent``).
        aux_params: early-exit auxiliary head params (required with
            ``intermittent``).
        slot0: absolute slot index of this run's first slot — the streamed
            driver passes its segment offset so ``it_src`` emission sources
            stay globally indexed and segment chains stay bitwise equal to
            one long run.
        telemetry: ``True`` (the default :func:`fleet_telemetry_spec` lane
            set) or a :class:`repro.obs.MetricsSpec` — registry lanes ride
            the scan carry and come back under ``res["telemetry"]`` (all
            int32; bitwise-equal across the three engines).  ``None``
            (default) keeps the engine bitwise-identical to the
            untelemetered path.
        telemetry_state0: a previous run's ``res["telemetry"]`` to resume
            from — merged host-side (:func:`repro.obs.metrics_merge`) after
            the run, so counters/histograms accumulate exactly across
            segments and gauges keep the latest level.
        tasks: optional (N,) int32 per-node task ids — the heterogeneous-
            task lane (HAR wearables + bearing monitors sharing one fleet).
            Defaults to :func:`repro.serving.fleet_lanes.
            fleet_task_assignment` when only ``task`` is given.
        task: optional :class:`repro.serving.fleet_lanes.TaskLaneConfig` —
            names, per-task cost scales (the WHOLE decision ladder and the
            intermittent lane's stage costs scale per node), and the
            ``per_task_host`` switch (``host_params`` then arrives as one
            tree per task and each node infers through its task's weights).
            Adds per-task splits ``completed_by_task``/
            ``deadline_miss_by_task`` (and ``correct_by_task``/
            ``accuracy_by_task`` with labels) to the aggregates.  ``None``
            (with ``tasks=None``) keeps the engine bitwise-identical to
            the homogeneous fleet.
        coresets: also return every node-slot's coresets as the scan
            computed them, under ``res["coresets"]``: ``centers`` (S, N, C,
            k, 2), ``radii`` and ``counts`` (S, N, C, k) after the AAC
            mask, and ``samp_idx`` (S, N, m), ``samp_vals`` (S, N, m, C),
            ``samp_mean`` and ``samp_var`` (S, N, C) — what a D3 or D4 node
            sends, for :func:`repro.host.fleet_slot_entries`.  ``False``
            (default) leaves the engine's program unchanged.

    Returns a dict of per-node traces, time-major:
        ``decisions``/``payload_bytes``/``stored_uj``/``k_trace``: (S, N),
        ``logits``/``preds``: (S, N, L) / (S, N),
        ``alive``/``brownout``: (S, N) bool — the EMITTED per-slot alive
            lane (exogenous ∧ ¬browned_out) and the brown-out flag each
            slot was entered with,
        ``bytes_on_wire``: () total payload bytes the fleet transmitted
            (float32; ``bytes_on_wire_i32`` is the exact (2,) int32
            [hi, lo] pair — combine with :func:`wire_bytes_exact`),
        ``decision_histogram``: (N_DECISIONS,) int32 counts over alive slots,
        ``completed``/``alive_slots``: () int32, ``completed_frac``: (),
        ``brownout_slots``/``brownout_events``: () int32 — slots suppressed
            by the hysteresis and brown-out onsets,
        ``fleet_accuracy``/``correct``: () when ``labels`` is given,
        ``raw_bytes_per_window``: () the uncompressed (T, C) baseline per
            window (all channels, the benchmarks' raw-equivalent convention),
        ``final_state``: stacked ``SeekerNodeState``.

    With ``intermittent`` the dict additionally carries the lane traces
    ``it_emit`` (S, N) int32 (0 none / 1 early exit / 2 full depth),
    ``it_label``/``it_src``/``it_stage``/``it_conf`` (S, N), the counters
    ``it_full``/``it_early`` (and, with labels, ``correct_ladder``/
    ``it_correct_full``/``it_correct_early``), and ``final_intermittent``
    (stacked :class:`~repro.serving.edge_host.IntermittentState`) for
    resuming; ``correct`` then sums ladder + lane completions, each scored
    against its source slot's label.
    """
    with obs_trace.span("fleet.prep", cat="fleet"):
        costs = costs or EnergyCosts()
        key = key if key is not None else jax.random.PRNGKey(0)
        n, s = harvest.shape
        assert windows.ndim in (3, 4), \
            f"windows must be (S,T,C) or (N,S,T,C), got {windows.shape}"
        shared_stream = windows.ndim == 3
        if shared_stream:
            assert windows.shape[0] == s, (windows.shape, s)
            xs_windows = windows                              # (S, T, C)
        else:
            assert windows.shape[:2] == (n, s), (windows.shape, n, s)
            xs_windows = jnp.moveaxis(windows, 0, 1)          # (S, N, T, C)
        t = windows.shape[-2]
        labels, per_node_labels = _resolve_labels(labels, s, n, shared_stream)
        alive_t = _resolve_alive(alive, n, s).T               # (S, N)

        state0 = _stack_pad_state(state0, n, 0, predictor_window, initial_uj)
        keys0 = node_keys if node_keys is not None else fleet_node_keys(key, n)
        browned0 = _resolve_brownout0(brownout_state0, state0, brownout, n)
        _validate_intermittent_args(intermittent, intermittent_state0,
                                    aux_params, n)
        tasks, task = _resolve_tasks(tasks, task, n)
        host_params = _resolve_task_host(task, host_params)
        tel_spec = _resolve_telemetry(telemetry, intermittent, task)
        run_fn = _build_fleet_run(har_cfg, costs, quant_bits, k_max, m_samples,
                                  corr_threshold, shared_stream, node_block,
                                  brownout, donate, intermittent, tel_spec,
                                  task, coresets)
        it0 = None
        if intermittent is not None:
            it0 = (intermittent_state0 if intermittent_state0 is not None
                   else intermittent_fleet_init(n, har_cfg))
        xs_slots = jnp.arange(slot0, slot0 + s, dtype=jnp.int32)
        harvest_t = harvest.T                                 # (S, N)
    with obs_trace.span("fleet.run", cat="fleet"):
        traces, final = run_fn(
            state0, keys0, browned0, it0, tasks, xs_windows, harvest_t,
            alive_t, xs_slots, signatures, qdnn_params, host_params,
            gen_params, aac_table, aux_params)
    with obs_trace.span("fleet.aggregates", cat="fleet"):
        final_state, final_keys = final.node, final.keys
        final_brownout, final_intermittent = final.brownout, final.intermittent
        tel_delta = final.telemetry

        aggs = _fleet_aggregates(traces, alive_t, labels, per_node_labels,
                                 intermittent, slot0, tasks=tasks, task=task)
        out = {
            "decisions": traces["decision"],                      # (S, N)
            "payload_bytes": traces["payload"],                   # (S, N)
            "stored_uj": traces["stored"],                        # (S, N)
            "k_trace": traces["k"],                               # (S, N)
            "logits": traces["logits"],                           # (S, N, L)
            "preds": jnp.argmax(traces["logits"], axis=-1),       # (S, N)
            "alive": traces["alive"],                             # (S, N)
            "brownout": traces["brownout"],                       # (S, N)
            "bytes_on_wire": aggs["bytes_on_wire"],
            "bytes_on_wire_i32": aggs["bytes_on_wire_i32"],
            "decision_histogram": aggs["decision_histogram"],
            "completed": aggs["completed"],
            "alive_slots": aggs["alive_slots"],
            "brownout_slots": aggs["brownout_slots"],
            "brownout_events": aggs["brownout_events"],
            "completed_frac": aggs["completed"]
                / jnp.maximum(aggs["alive_slots"], 1),
            "raw_bytes_per_window": jnp.asarray(
                float(raw_payload_bytes(t)) * windows.shape[-1], jnp.float32),
            "final_state": final_state,
            "final_keys": final_keys,
            "final_brownout": final_brownout,
        }
        if coresets:
            out["coresets"] = traces["coresets"]
        if tel_spec is not None:
            out["telemetry"] = metrics_merge(tel_spec, telemetry_state0,
                                             tel_delta)
            out["telemetry_spec"] = tel_spec
        if intermittent is not None:
            out.update({
                "it_emit": traces["it_emit"],                     # (S, N)
                "it_label": traces["it_label"],                   # (S, N)
                "it_conf": traces["it_conf"],                     # (S, N)
                "it_src": traces["it_src"],                       # (S, N)
                "it_stage": traces["it_stage"],                   # (S, N)
                "it_full": aggs["it_full"],
                "it_early": aggs["it_early"],
                "final_intermittent": final_intermittent,
            })
        if labels is not None:
            out["correct"] = aggs["correct"]
            out["fleet_accuracy"] = (aggs["correct"]
                                     / jnp.maximum(aggs["completed"], 1))
            if intermittent is not None:
                out["correct_ladder"] = aggs["correct_ladder"]
                out["it_correct_full"] = aggs["it_correct_full"]
                out["it_correct_early"] = aggs["it_correct_early"]
        if task is not None:
            out["task_names"] = task.names
            out["tasks"] = tasks
            out["completed_by_task"] = aggs["completed_by_task"]
            out["deadline_miss_by_task"] = aggs["deadline_miss_by_task"]
            if labels is not None:
                out["correct_by_task"] = aggs["correct_by_task"]
                out["accuracy_by_task"] = (
                    aggs["correct_by_task"]
                    / jnp.maximum(aggs["completed_by_task"], 1))
        return out


def seeker_fleet_simulate_sharded(
        windows: jnp.ndarray, harvest: jnp.ndarray, *,
        signatures, qdnn_params, host_params, gen_params,
        har_cfg: HARConfig, mesh=None,
        aac_table: AACTable | None = None,
        costs: EnergyCosts | None = None,
        key: jax.Array | None = None, quant_bits: int = 16,
        k_max: int = 12, m_samples: int = 20, corr_threshold: float = 0.95,
        predictor_window: int = 8, initial_uj: float = 50.0,
        state0: SeekerNodeState | None = None,
        node_keys: jax.Array | None = None,
        labels: jnp.ndarray | None = None,
        alive: jnp.ndarray | None = None,
        brownout: BrownoutConfig | None = None,
        brownout_state0: jnp.ndarray | None = None,
        node_block: int | None = None, donate: bool = True,
        intermittent: IntermittentConfig | None = None,
        intermittent_state0: IntermittentState | None = None,
        aux_params: dict | None = None,
        slot0: int = 0,
        telemetry=None,
        telemetry_state0: dict | None = None,
        tasks: jnp.ndarray | None = None,
        task: TaskLaneConfig | None = None):
    """:func:`seeker_fleet_simulate` with the node axis sharded over a mesh.

    The fleet's node dim is split over the mesh axes the ``"nodes"`` logical
    axis resolves to (:data:`repro.sharding.FLEET_RULES`: ("pod", "data"),
    axes absent from ``mesh`` dropped); the signature bank and all model
    params are replicated.  The whole time scan runs inside the shard_map
    manual region — per-node state never crosses shards; ``bytes_on_wire``,
    ``decision_histogram``, ``completed_frac`` (and ``fleet_accuracy`` when
    ``labels`` is given) are the only collectives, reduced with ``psum``.

    Fleets with N not divisible by the mesh quantum are padded with inert
    nodes — zero harvest, default state, masked out of every aggregate — and
    the padding is sliced off the returned traces.  Integer and energy traces
    (decisions, payload bytes, stored µJ, k) are bit-identical to the
    single-device engine for any N; host logits additionally need a common
    ``node_block`` in both engines to pin XLA's batch-shape-dependent matmul
    lowering (see :func:`_make_fleet_step`), otherwise they match to ~1e-6.

    Args (beyond :func:`seeker_fleet_simulate`):
        mesh: a ``jax.sharding.Mesh``; default is a 1-D ("data",) mesh over
            every visible device.
        labels: optional ground truth enabling the ``fleet_accuracy``
            aggregate: (S,) for a shared stream, or per-node (S, N) tracks
            (sharded over the node axes, padded like harvest).  A shared
            (S,) track with per-node window streams raises.
        alive: optional (N, S) bool churn trace — sharded over the node
            axes; padding nodes are permanently dead.
        brownout: optional :class:`repro.core.energy.BrownoutConfig` — the
            endogenous brown-out lane (see :func:`seeker_fleet_simulate`).
            The flag lives in each shard's local carry; ``brownout_slots``
            and ``brownout_events`` join the psum'd aggregate set.  Padding
            nodes are exogenously dead, so their flag stays frozen — they
            never brown "in" and never count.
        intermittent: optional staged-inference lane (see
            :func:`seeker_fleet_simulate`) — the lane state is sharded over
            the node axes like every other per-node carry; padding nodes
            start (and stay) inert.  Lane emission counters and the
            source-slot-scored accuracy splits join the psum'd set.  A
            common ``node_block`` in both engines makes lane traces
            bit-identical across shard layouts, same as the host logits.
        tasks/task: the heterogeneous-task lane (see
            :func:`seeker_fleet_simulate`).  Task ids are sharded over the
            node axes like harvest; padding nodes get task 0 but are masked
            out of every per-task count, so ``completed_by_task``/
            ``deadline_miss_by_task`` (and ``correct_by_task`` with labels)
            are psum-exact equals of the single-device engine's.

    Extra returns: ``decision_histogram`` (N_DECISIONS,) int32 fleet-wide
    decision counts over alive slots, ``completed``/``alive_slots`` () int32,
    ``brownout_slots``/``brownout_events`` () int32 (psum'd, exactly equal
    to the single-device engine's), ``bytes_on_wire_i32`` (2,) int32 exact
    byte pair, ``completed_frac`` (), ``fleet_accuracy``/``correct`` () when
    ``labels`` is given, ``padded_nodes`` (python int), ``node_axes``
    (python tuple of mesh axis names).
    """
    with obs_trace.span("fleet.prep", cat="fleet"):
        costs = costs or EnergyCosts()
        key = key if key is not None else jax.random.PRNGKey(0)
        if mesh is None:
            mesh = make_mesh_compat((jax.device_count(),), ("data",))
        axis_names, quantum = node_mesh_axes(mesh)
        if not axis_names:
            raise ValueError(
                f"mesh {mesh.axis_names} has none of the FLEET_RULES node "
                f"axes")

        n, s = harvest.shape
        assert windows.ndim in (3, 4), \
            f"windows must be (S,T,C) or (N,S,T,C), got {windows.shape}"
        shared_stream = windows.ndim == 3
        pad = (-n) % quantum
        if shared_stream:
            assert windows.shape[0] == s, (windows.shape, s)
            xs_windows = windows                              # (S, T, C)
        else:
            assert windows.shape[:2] == (n, s), (windows.shape, n, s)
            xs_windows = jnp.moveaxis(windows, 0, 1)          # (S, N, T, C)
            if pad:   # inert nodes see all-zero windows (corr 0, masked)
                xs_windows = jnp.pad(xs_windows,
                                     ((0, 0), (0, pad)) + ((0, 0),) * 2)
        t = windows.shape[-2]
        labels, per_node_labels = _resolve_labels(labels, s, n, shared_stream)

        state_full = _stack_pad_state(state0, n, pad, predictor_window,
                                      initial_uj)
        keys0 = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(n + pad))
        if node_keys is not None:        # resume: real nodes continue their
            keys0 = keys0.at[:n].set(node_keys)     # streams, pad keys inert
        harvest_t = jnp.pad(harvest, ((0, pad), (0, 0))).T        # (S, N+pad)
        # churn trace, padding nodes permanently dead (their ladder never runs)
        alive_t = jnp.pad(_resolve_alive(alive, n, s),
                          ((0, pad), (0, 0))).T                   # (S, N+pad)
        mask = jnp.arange(n + pad) < n
        if labels is None:
            labels_arr = jnp.zeros((s,), jnp.int32)
        elif per_node_labels:            # pad like harvest: inert nodes' track
            labels_arr = jnp.pad(labels, ((0, 0), (0, pad)))      # (S, N+pad)
        else:
            labels_arr = labels

        # brown-out flag, padding nodes forced awake: they are exogenously dead
        # (frozen flag), so they can never brown "in" nor trickle back out
        browned0 = jnp.pad(
            _resolve_brownout0(brownout_state0, state_full, brownout, n),
            (0, pad))
        _validate_intermittent_args(intermittent, intermittent_state0,
                                    aux_params, n)
        tasks, task = _resolve_tasks(tasks, task, n)
        host_params = _resolve_task_host(task, host_params)
        if tasks is not None and pad:   # padding nodes run task 0, masked out
            tasks = jnp.pad(tasks, (0, pad))
        tel_spec = _resolve_telemetry(telemetry, intermittent, task)
        run_fn = _build_fleet_run_sharded(
            mesh, axis_names, har_cfg, costs, quant_bits, k_max, m_samples,
            corr_threshold, shared_stream, per_node_labels, node_block,
            brownout, donate, intermittent, tel_spec, task)
        it0 = None
        if intermittent is not None:
            it0 = (intermittent_state0 if intermittent_state0 is not None
                   else intermittent_fleet_init(n, har_cfg))
            if pad:   # inert lane rows for padding nodes (never engage: dead)
                filler = intermittent_fleet_init(pad, har_cfg)
                it0 = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b], axis=0), it0, filler)
        xs_slots = jnp.arange(slot0, slot0 + s, dtype=jnp.int32)
    with obs_trace.span("fleet.run", cat="fleet"):
        traces, final, aggs = run_fn(
            state_full, keys0, browned0, it0, tasks, xs_windows, harvest_t,
            alive_t, xs_slots, mask, labels_arr, signatures, qdnn_params,
            host_params, gen_params, aac_table, aux_params)
    with obs_trace.span("fleet.aggregates", cat="fleet"):
        final_state, final_keys = final.node, final.keys
        final_brownout, final_intermittent = final.brownout, final.intermittent
        tel_delta = final.telemetry

        out = {
            "decisions": traces["decision"][:, :n],               # (S, N)
            "payload_bytes": traces["payload"][:, :n],            # (S, N)
            "stored_uj": traces["stored"][:, :n],                 # (S, N)
            "k_trace": traces["k"][:, :n],                        # (S, N)
            "logits": traces["logits"][:, :n],                    # (S, N, L)
            "preds": jnp.argmax(traces["logits"][:, :n], axis=-1),
            "alive": traces["alive"][:, :n],                      # (S, N)
            "brownout": traces["brownout"][:, :n],                # (S, N)
            "bytes_on_wire": aggs["bytes_on_wire"],
            "bytes_on_wire_i32": aggs["bytes_on_wire_i32"],
            "decision_histogram": aggs["decision_histogram"],
            "completed": aggs["completed"],
            "alive_slots": aggs["alive_slots"],
            "brownout_slots": aggs["brownout_slots"],
            "brownout_events": aggs["brownout_events"],
            "completed_frac": aggs["completed"]
                / jnp.maximum(aggs["alive_slots"], 1),
            "raw_bytes_per_window": jnp.asarray(
                float(raw_payload_bytes(t)) * windows.shape[-1], jnp.float32),
            "final_state": jax.tree_util.tree_map(lambda a: a[:n],
                                                  final_state),
            "final_keys": final_keys[:n],
            "final_brownout": final_brownout[:n],
            "padded_nodes": pad,
            "node_axes": axis_names,
        }
        if tel_spec is not None:
            out["telemetry"] = metrics_merge(tel_spec, telemetry_state0,
                                             tel_delta)
            out["telemetry_spec"] = tel_spec
        if intermittent is not None:
            out.update({
                "it_emit": traces["it_emit"][:, :n],              # (S, N)
                "it_label": traces["it_label"][:, :n],            # (S, N)
                "it_conf": traces["it_conf"][:, :n],              # (S, N)
                "it_src": traces["it_src"][:, :n],                # (S, N)
                "it_stage": traces["it_stage"][:, :n],            # (S, N)
                "it_full": aggs["it_full"],
                "it_early": aggs["it_early"],
                "final_intermittent": jax.tree_util.tree_map(
                    lambda a: a[:n], final_intermittent),
            })
        if labels is not None:
            out["correct"] = aggs["correct"]
            out["fleet_accuracy"] = (aggs["correct"]
                                     / jnp.maximum(aggs["completed"], 1))
            if intermittent is not None:
                out["correct_ladder"] = aggs["correct_ladder"]
                out["it_correct_full"] = aggs["it_correct_full"]
                out["it_correct_early"] = aggs["it_correct_early"]
        if task is not None:
            out["task_names"] = task.names
            out["tasks"] = tasks[:n]
            out["completed_by_task"] = aggs["completed_by_task"]
            out["deadline_miss_by_task"] = aggs["deadline_miss_by_task"]
            if labels is not None:
                out["correct_by_task"] = aggs["correct_by_task"]
                out["accuracy_by_task"] = (
                    aggs["correct_by_task"]
                    / jnp.maximum(aggs["completed_by_task"], 1))
        return out


def seeker_fleet_simulate_streamed(
        windows, harvest: jnp.ndarray, *, chunk: int,
        signatures, qdnn_params, host_params, gen_params,
        har_cfg: HARConfig, mesh=None,
        aac_table: AACTable | None = None,
        costs: EnergyCosts | None = None,
        key: jax.Array | None = None, quant_bits: int = 16,
        k_max: int = 12, m_samples: int = 20, corr_threshold: float = 0.95,
        predictor_window: int = 8, initial_uj: float = 50.0,
        state0: SeekerNodeState | None = None,
        node_keys: jax.Array | None = None,
        labels: jnp.ndarray | None = None,
        alive: jnp.ndarray | None = None,
        brownout: BrownoutConfig | None = None,
        brownout_state0: jnp.ndarray | None = None,
        node_block: int | None = None, donate: bool = True,
        intermittent: IntermittentConfig | None = None,
        intermittent_state0: IntermittentState | None = None,
        aux_params: dict | None = None,
        telemetry=None,
        telemetry_state0: dict | None = None,
        tasks: jnp.ndarray | None = None,
        task: TaskLaneConfig | None = None):
    """Feed the fleet scan in ``chunk``-slot window segments instead of
    materializing the whole (N, S, T, C) stream up front.

    The driver around the resume contract: each segment runs through
    :func:`seeker_fleet_simulate` (or the sharded engine when ``mesh`` is
    given) with the previous segment's ``final_state``/``final_keys``, so
    the chain is *bitwise* one long run — decisions, payload bytes, stored
    µJ, logits and final keys are identical to a single S-slot call — while
    peak window memory is O(N·chunk·T·C) instead of O(N·S·T·C).  Every
    segment reuses the engines' compile cache (one compiled scan per
    distinct segment length: ``S % chunk`` adds at most one more shape).

    Args (beyond the engines'):
        windows: the stream *source* — either a full array ((S, T, C) shared
            or (N, S, T, C) per-node; the driver slices it) or a callable
            ``windows(start, stop) -> (stop-start, T, C) | (N, stop-start,
            T, C)`` producing each segment on demand.  The callable form is
            the point of streaming: only one chunk of windows ever exists.
        chunk: slots per segment (the last segment may be shorter).
        mesh: run segments through :func:`seeker_fleet_simulate_sharded`.
        brownout: endogenous brown-out config — the flag rides the
            ``state0``/``node_keys`` resume contract bitwise: each segment
            resumes from the previous segment's ``final_brownout``.
        intermittent: staged intermittent-inference lane — the suspended
            activations ride the resume contract too: each segment resumes
            from the previous segment's ``final_intermittent``, and each
            segment is launched at its absolute ``slot0`` offset so a staged
            inference suspended in one segment and emitted in the next keeps
            its globally indexed source slot.  Accuracy for lane emissions is
            rescored over the CONCATENATED traces (a segment cannot see the
            labels of windows captured before its first slot), so
            ``correct``/``fleet_accuracy`` again exactly match one long run.
        telemetry: registry lanes (see :func:`seeker_fleet_simulate`) — each
            segment resumes from the previous segment's ``res["telemetry"]``
            (the :func:`repro.obs.metrics_merge` chain), so the final lanes
            are bitwise-equal to one long telemetered run.
        tasks/task: the heterogeneous-task lane (see
            :func:`seeker_fleet_simulate`) — task ids are static per-node,
            so every segment reuses the same resolved assignment; per-task
            completion/miss counters sum exactly, and ``correct_by_task`` is
            rescored over the concatenated traces (like ``correct``) so
            cross-segment staged emissions land in the right task bucket.

    Returns the engine dict with traces concatenated over time, counter
    aggregates (``decision_histogram``, ``completed``, ``alive_slots``,
    ``brownout_slots``, ``brownout_events``, ``correct``, the
    ``bytes_on_wire_i32`` exact pair) summed exactly, float aggregates
    (``bytes_on_wire``) summed per segment, and
    ``completed_frac``/``fleet_accuracy`` recomputed from the summed
    counters; plus ``n_chunks``.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    n, s = harvest.shape
    if s < 1:
        raise ValueError(
            f"cannot stream an empty deployment: harvest is (N, S)="
            f"({n}, {s}) — S must be >= 1 slot")
    if callable(windows):
        window_fn = windows
    else:
        arr = jnp.asarray(windows)
        if arr.ndim == 3:
            window_fn = lambda a, b: arr[a:b]                 # noqa: E731
        else:
            window_fn = lambda a, b: arr[:, a:b]              # noqa: E731
    labels_full = None if labels is None else jnp.asarray(labels)
    alive_full = None if alive is None else _resolve_alive(alive, n, s)
    tasks, task = _resolve_tasks(tasks, task, n)

    kw = dict(signatures=signatures, qdnn_params=qdnn_params,
              host_params=host_params, gen_params=gen_params,
              har_cfg=har_cfg, aac_table=aac_table, costs=costs, key=key,
              quant_bits=quant_bits, k_max=k_max, m_samples=m_samples,
              corr_threshold=corr_threshold,
              predictor_window=predictor_window, initial_uj=initial_uj,
              brownout=brownout, node_block=node_block, donate=donate,
              intermittent=intermittent, aux_params=aux_params,
              telemetry=telemetry, tasks=tasks, task=task)
    if mesh is not None:
        kw["mesh"] = mesh
    engine = (seeker_fleet_simulate if mesh is None
              else seeker_fleet_simulate_sharded)

    # the segment keys to concatenate/sum come from the lane registry — a
    # new lane that declares trace_keys/counter_keys streams automatically
    active = _active_lanes(intermittent, task, brownout)
    trace_keys = list(fleet_trace_keys(active))
    counter_keys = list(fleet_counter_keys(active))

    tel_spec = _resolve_telemetry(telemetry, intermittent, task)
    state, keys, browned = state0, node_keys, brownout_state0
    it_state = intermittent_state0
    tel_state = telemetry_state0
    parts: list[dict] = []
    counters: dict = {}
    bytes_on_wire = jnp.zeros((), jnp.float32)
    res = None
    for start in range(0, s, chunk):
        stop = min(start + chunk, s)
        seg_kw = dict(kw)
        if labels_full is not None:
            seg_kw["labels"] = labels_full[start:stop]
        if alive_full is not None:
            seg_kw["alive"] = alive_full[:, start:stop]
        if intermittent is not None:
            seg_kw["intermittent_state0"] = it_state
            seg_kw["slot0"] = start
        if tel_spec is not None:
            seg_kw["telemetry_state0"] = tel_state
        with obs_trace.span("fleet.segment", cat="fleet",
                            args={"start": start, "stop": stop},
                            flush=lambda: res["decisions"]):
            res = engine(window_fn(start, stop), harvest[:, start:stop],
                         state0=state, node_keys=keys,
                         brownout_state0=browned, **seg_kw)
        state, keys = res["final_state"], res["final_keys"]
        browned = res["final_brownout"]
        if intermittent is not None:
            it_state = res["final_intermittent"]
        if tel_spec is not None:
            tel_state = res["telemetry"]
        parts.append({k: res[k] for k in trace_keys})
        for k in counter_keys:
            if k in res:
                counters[k] = counters.get(k, 0) + res[k]
        # the exact byte pair needs its carry propagated each segment: a
        # segment's lo digit is < N * 2**16, so adding it to an ALREADY
        # NORMALIZED lo (< 2**16) stays exact in int32 for N < 32768 — the
        # same node bound as the pair itself — while an un-normalized
        # running lo would overflow after ~2**15/N segments
        pair = counters.get("bytes_on_wire_i32",
                            jnp.zeros((2,), jnp.int32)) \
            + res["bytes_on_wire_i32"]
        counters["bytes_on_wire_i32"] = jnp.stack(
            [pair[0] + (pair[1] >> 16), pair[1] & 0xFFFF])
        bytes_on_wire = bytes_on_wire + res["bytes_on_wire"]

    out = {k: jnp.concatenate([p[k] for p in parts], axis=0)
           for k in parts[0]}
    out.update(counters)
    out.update({
        "bytes_on_wire": bytes_on_wire,
        "completed_frac": counters["completed"]
            / jnp.maximum(counters["alive_slots"], 1),
        "raw_bytes_per_window": res["raw_bytes_per_window"],
        "final_state": state,
        "final_keys": keys,
        "final_brownout": browned,
        "n_chunks": -(-s // chunk),
    })
    if tel_spec is not None:
        out["telemetry"] = tel_state
        out["telemetry_spec"] = tel_spec
    if intermittent is not None:
        out["final_intermittent"] = it_state
    if "correct" in counters:
        if intermittent is not None:
            # a segment cannot score an emission whose window was captured
            # in an EARLIER segment (its label is out of the segment's
            # view), so the per-segment it_correct counters undercount
            # exactly the cross-segment completions — rescore the lane over
            # the concatenated traces, where every source slot is visible
            rel = out["it_src"]                  # driver runs from slot 0
            valid = (out["it_emit"] > 0) & out["alive"] & (rel >= 0)
            rel_c = jnp.clip(rel, 0, s - 1)
            lab = (jnp.take_along_axis(labels_full.astype(jnp.int32), rel_c,
                                       axis=0)
                   if labels_full.ndim == 2
                   else labels_full.astype(jnp.int32)[rel_c])
            it_ok = (out["it_label"] == lab) & valid
            out["it_correct_full"] = jnp.sum(
                (it_ok & (out["it_emit"] == 2)).astype(jnp.int32))
            out["it_correct_early"] = jnp.sum(
                (it_ok & (out["it_emit"] == 1)).astype(jnp.int32))
            out["correct"] = (counters["correct_ladder"]
                              + out["it_correct_full"]
                              + out["it_correct_early"])
        out["fleet_accuracy"] = (out["correct"]
                                 / jnp.maximum(counters["completed"], 1))
    if task is not None:
        out["task_names"] = task.names
        out["tasks"] = tasks
        if labels_full is not None:
            # like ``correct``: per-segment correct_by_task counters cannot
            # see cross-segment staged emissions, so rescore the split once
            # over the concatenated traces (integer counts — exact)
            tasks_b = jnp.broadcast_to(tasks[None, :], out["alive"].shape)
            lab_t = labels_full.astype(jnp.int32)
            ok = out["preds"] == (lab_t if lab_t.ndim == 2
                                  else lab_t[:, None])
            if intermittent is None:
                sent = (out["decisions"] != DEFER) & out["alive"]
                out["correct_by_task"] = categorical_counts(
                    tasks_b, task.n_tasks, ok & sent)
            else:
                sent = ((out["decisions"] != DEFER)
                        & (out["decisions"] != D6_PARTIAL) & out["alive"])
                ladder_sent = sent & (out["decisions"] <= D4_SAMPLING)
                out["correct_by_task"] = (
                    categorical_counts(tasks_b, task.n_tasks,
                                       ok & ladder_sent)
                    + categorical_counts(tasks_b, task.n_tasks,
                                         it_ok & (out["it_emit"] == 2))
                    + categorical_counts(tasks_b, task.n_tasks,
                                         it_ok & (out["it_emit"] == 1)))
            out["accuracy_by_task"] = (
                out["correct_by_task"]
                / jnp.maximum(counters["completed_by_task"], 1))
    if mesh is not None:
        out["padded_nodes"] = res["padded_nodes"]
        out["node_axes"] = res["node_axes"]
    return out
