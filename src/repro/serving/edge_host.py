"""Seeker edge-host serving: the paper's full decision flow (Fig. 8),
single-node and distributed (pod-axis) variants.

Single-node simulation (:func:`seeker_simulate`) reproduces the paper's
system evaluation: per sensing window, the node

  1. correlates against the signature bank (D0 memoization),
  2. forecasts harvestable energy (moving-average predictor),
  3. picks D0-D4 / DEFER from the Table-2 cost ladder,
  4. executes: quantized DNN on-node (D2) or coreset offload (D3/D4) with
     host-side recovery + full-precision DNN,
  5. ensembles across sensors.

Distributed variant (:func:`edge_host_serve_step`): pods pair up as
edge/host tiers — each pod builds cluster coresets for its local sensor
batch, ships the *quantized coreset payload* (centers/radii/counts, the 42-B
wire format scaled up) to its peer over ``collective_permute`` across the
"pod" mesh axis, recovers the peer's payload, and runs host inference.  The
collective moves coreset bytes instead of raw windows: the paper's 5.7-8.9x
reduction shows up directly in the dry-run's collective-permute operand
sizes (see benchmarks/comm_volume.py).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.aac import AACTable, select_k
from ..core.coreset import (channel_cluster_coresets, cluster_payload_bytes,
                            kmeans_coreset, points_from_window,
                            raw_payload_bytes, sampling_payload_bytes)
from ..core.decision import (D0_MEMO, D2_DNN_QUANT, D3_CLUSTER, D4_SAMPLING,
                             DEFER, D6_PARTIAL, D7_EARLY_EXIT, D8_STAGED_FULL,
                             IntermittentConfig, choose_decision,
                             decision_energy)
from ..core.energy import (BrownoutConfig, EnergyCosts, PredictorState,
                           predictor_forecast, predictor_init,
                           predictor_update, supercap_step,
                           supercap_step_direct)
from ..core.memo import signature_correlations
from ..core.recovery import (GeneratorParams, recover_cluster_window,
                             recover_sampling_window)
from ..core.coreset import importance_coreset
from ..models.har import (HARConfig, har_act_buffer, har_apply,
                          har_apply_aux, har_apply_quantized,
                          har_apply_stage)
from ..obs.trace import scope

__all__ = ["SeekerNodeState", "seeker_node_init", "seeker_sensor_step",
           "seeker_sensor_step_given_corr", "seeker_host_step",
           "seeker_simulate", "seeker_simulate_reference",
           "edge_host_serve_step", "fleet_serve_step", "WirePayload",
           "encode_wire_coresets", "decode_wire_coresets",
           "wire_payload_nbytes", "wire_payload_to_bytes",
           "wire_payload_from_bytes", "WireSamplePayload",
           "encode_wire_samples", "decode_wire_samples",
           "wire_sample_nbytes", "IntermittentState",
           "intermittent_node_init", "intermittent_fleet_init",
           "IntermittentLaneOut", "intermittent_lane_step"]


class SeekerNodeState(NamedTuple):
    stored_uj: jnp.ndarray          # supercap charge
    predictor: PredictorState
    prev_label: jnp.ndarray         # temporal continuity for AAC


def seeker_node_init(predictor_window: int = 8,
                     initial_uj: float = 50.0) -> SeekerNodeState:
    return SeekerNodeState(
        stored_uj=jnp.asarray(initial_uj, jnp.float32),
        predictor=predictor_init(predictor_window),
        prev_label=jnp.zeros((), jnp.int32))


class SensorStepOut(NamedTuple):
    decision: jnp.ndarray           # () int32
    label_or_neg: jnp.ndarray       # () int32: >=0 for D0/D2 results
    logits: jnp.ndarray             # (L,) on-node logits (D2) or zeros
    coreset_centers: jnp.ndarray    # (k_max, D)
    coreset_radii: jnp.ndarray      # (k_max,)
    coreset_counts: jnp.ndarray     # (k_max,)
    coreset_k: jnp.ndarray          # () int32 — AAC-selected k
    samp_idx: jnp.ndarray           # (m,) int32 — D4 payload
    samp_vals: jnp.ndarray          # (m, C)
    samp_mean: jnp.ndarray          # (C,)
    samp_var: jnp.ndarray           # (C,)
    payload_bytes: jnp.ndarray      # () float
    state: SeekerNodeState


def seeker_sensor_step(window: jnp.ndarray, state: SeekerNodeState,
                       harvested_uj: jnp.ndarray, *, signatures: jnp.ndarray,
                       qdnn_params: dict, har_cfg: HARConfig,
                       aac_table: AACTable | None, costs: EnergyCosts,
                       key: jax.Array, k_max: int = 12, m_samples: int = 20,
                       quant_bits: int = 16,
                       corr_threshold: float = 0.95) -> SensorStepOut:
    """One sensing slot on the EH node (paper Fig. 8, all branches traced)."""
    corr = signature_correlations(window, signatures)
    return seeker_sensor_step_given_corr(
        window, state, harvested_uj, corr, qdnn_params=qdnn_params,
        har_cfg=har_cfg, aac_table=aac_table, costs=costs, key=key,
        k_max=k_max, m_samples=m_samples, quant_bits=quant_bits,
        corr_threshold=corr_threshold)


def seeker_sensor_step_given_corr(
        window: jnp.ndarray, state: SeekerNodeState,
        harvested_uj: jnp.ndarray, corr: jnp.ndarray, *, qdnn_params: dict,
        har_cfg: HARConfig, aac_table: AACTable | None, costs: EnergyCosts,
        key: jax.Array, k_max: int = 12, m_samples: int = 20,
        quant_bits: int = 16, corr_threshold: float = 0.95,
        strict_energy: bool = False,
        cost_scale: jnp.ndarray | None = None) -> SensorStepOut:
    """Sensor step with the signature correlations precomputed.

    The fleet engine computes ``corr`` for ALL nodes at once through the
    batched :func:`repro.kernels.signature_corr_op` hot path, then vmaps this
    function over nodes; the single-node path computes it per window.

    ``strict_energy`` switches the ladder to store-and-execute accounting:
    the decision must be payable from ``stored + harvested`` this slot (the
    forecast still ranks AAC's k but cannot mint energy), and the storage
    update uses :func:`repro.core.energy.supercap_step_direct` so debt is
    never clip-forgiven.  ``False`` keeps the legacy path bitwise.

    ``cost_scale`` is the heterogeneous-task lane's per-node ladder scale
    (see :class:`repro.serving.fleet_lanes.TaskLaneConfig`); ``None`` keeps
    the homogeneous-fleet jaxpr bitwise.
    """
    with scope("seeker.decide"):
        max_corr = jnp.max(corr)
        memo_label = jnp.argmax(corr).astype(jnp.int32)

        predictor = predictor_update(state.predictor, harvested_uj)
        forecast = predictor_forecast(predictor)
        outcome = choose_decision(
            max_corr, state.stored_uj, forecast, costs,
            corr_threshold=corr_threshold,
            harvested_uj=harvested_uj if strict_energy else None,
            cost_scale=cost_scale)
        decision = outcome.decision

    # --- D2: quantized DNN on-node (executed unconditionally, masked out) ---
    with scope("seeker.edge_dnn"):
        logits = har_apply_quantized(qdnn_params, window[None],
                                     quant_bits)[0]
        dnn_label = jnp.argmax(logits).astype(jnp.int32)

    # --- D3: AAC clustering coreset (per-channel, as the paper's FIFO) -----
    with scope("seeker.aac_coreset"):
        if aac_table is not None:
            k_sel = select_k(aac_table, state.prev_label,
                             state.stored_uj + forecast)
        else:
            k_sel = jnp.asarray(k_max, jnp.int32)
        cs = channel_cluster_coresets(window, k=k_max, iters=4)  # (C, k, 2)
        # zero out clusters beyond the AAC-selected k (static k_max buffer)
        keep = jnp.arange(k_max) < k_sel
        centers = jnp.where(keep[None, :, None], cs.centers, 0.0)
        radii = jnp.where(keep[None, :], cs.radii, 0.0)
        counts = jnp.where(keep[None, :], cs.counts, 0)

    # --- D4: importance-sampling coreset -----------------------------------
    with scope("seeker.importance_coreset"):
        sc = importance_coreset(window, m_samples, key)

    # --- bookkeeping --------------------------------------------------------
    with scope("seeker.decide"):
        t = window.shape[0]
        c = window.shape[1] if window.ndim > 1 else 1
        bytes_by_decision = jnp.asarray([
            2.0,                                          # D0: a label
            2.0, 2.0,                                     # D1/D2: a result
            0.0,                                          # D3: AAC (below)
            float(sampling_payload_bytes(m_samples, channels=c)),
            0.0,                                          # DEFER
        ])
        aac_bytes = (k_sel.astype(jnp.float32) * 3.0
                     + jnp.ceil(k_sel.astype(jnp.float32) / 2.0)) * c
        payload = jnp.where(decision == D3_CLUSTER, aac_bytes,
                            bytes_by_decision[decision])

        if strict_energy:
            stored = supercap_step_direct(state.stored_uj, harvested_uj,
                                          outcome.spend)
        else:
            stored = supercap_step(state.stored_uj, harvested_uj,
                                   outcome.spend)
        label = jnp.where(decision == D0_MEMO, memo_label,
                          jnp.where(decision == D2_DNN_QUANT, dnn_label, -1))
        prev = jnp.where(label >= 0, label, state.prev_label)
        new_state = SeekerNodeState(stored_uj=stored, predictor=predictor,
                                    prev_label=prev)
        return SensorStepOut(
            decision=decision, label_or_neg=label.astype(jnp.int32),
            logits=jnp.where(decision == D2_DNN_QUANT, logits, 0.0),
            coreset_centers=centers, coreset_radii=radii,
            coreset_counts=counts, coreset_k=k_sel, samp_idx=sc.indices,
            samp_vals=sc.values, samp_mean=sc.mean, samp_var=sc.var,
            payload_bytes=payload, state=new_state)


def seeker_host_step(out: SensorStepOut, *, host_params: dict,
                     gen_params: GeneratorParams, har_cfg: HARConfig,
                     key: jax.Array, t: int) -> jnp.ndarray:
    """Host side: recover the offloaded representation and infer (D3/D4);
    pass through on-node results (D0/D2). Returns (n_classes,) logits."""
    from ..core.coreset import ClusterCoreset, SamplingCoreset

    k1, k2 = jax.random.split(key)
    cs = ClusterCoreset(out.coreset_centers, out.coreset_radii,
                        out.coreset_counts)
    with scope("seeker.recover_cluster"):
        win_cluster = recover_cluster_window(cs, k1, t)
    sc = SamplingCoreset(out.samp_idx, out.samp_vals,
                         jnp.ones_like(out.samp_idx, jnp.float32),
                         out.samp_mean, out.samp_var)
    with scope("seeker.recover_sampling"):
        win_sampling = recover_sampling_window(gen_params, sc, k2, t)

    with scope("seeker.host_dnn"):
        logit_cluster = har_apply(host_params, win_cluster[None])[0]
        logit_sampling = har_apply(host_params, win_sampling[None])[0]
        onehot = (jax.nn.one_hot(out.label_or_neg, logit_cluster.shape[-1])
                  * 8.0)                             # confident on-node result
        return jnp.where(out.decision == D3_CLUSTER, logit_cluster,
                         jnp.where(out.decision == D4_SAMPLING,
                                   logit_sampling,
                                   jnp.where(out.decision == DEFER,
                                             jnp.zeros_like(logit_cluster),
                                             onehot)))


# ---------------------------------------------------------------------------
# Intermittent-inference lane (decision codes D6/D7/D8)
# ---------------------------------------------------------------------------


class IntermittentState(NamedTuple):
    """Per-node staged-inference progress — the intermittent lane's slice of
    the fleet scan carry (see docs/RESUME_CONTRACT.md for the rules a carry
    lane must follow).

    ``active``: a staged inference is in flight (suspended or advancing).
    ``stage``: completed stages (1..3; 3 = logits ready, transmit pending).
    ``acts``: (A,) flat activation buffer holding the last completed stage's
    output (A = :func:`repro.models.har.har_act_buffer`).
    ``src_slot``: the GLOBAL slot index whose window is in flight — emissions
    are scored against this slot's label, not the emission slot's.
    """

    active: jnp.ndarray     # () / (N,) bool
    stage: jnp.ndarray      # () / (N,) int32
    acts: jnp.ndarray       # (A,) / (N, A) float32
    src_slot: jnp.ndarray   # () / (N,) int32


def intermittent_node_init(har_cfg: HARConfig) -> IntermittentState:
    """Idle single-node lane state (nothing in flight)."""
    return IntermittentState(
        active=jnp.zeros((), bool),
        stage=jnp.zeros((), jnp.int32),
        acts=jnp.zeros((har_act_buffer(har_cfg),), jnp.float32),
        src_slot=jnp.zeros((), jnp.int32))


def intermittent_fleet_init(n_nodes: int,
                            har_cfg: HARConfig) -> IntermittentState:
    """Stacked idle lane state for ``n_nodes`` (leading node axis)."""
    return IntermittentState(
        active=jnp.zeros((n_nodes,), bool),
        stage=jnp.zeros((n_nodes,), jnp.int32),
        acts=jnp.zeros((n_nodes, har_act_buffer(har_cfg)), jnp.float32),
        src_slot=jnp.zeros((n_nodes,), jnp.int32))


class IntermittentLaneOut(NamedTuple):
    engaged: jnp.ndarray       # () bool — the lane overrode this slot
    decision: jnp.ndarray      # () int32: D6/D7/D8 or DEFER
    spend: jnp.ndarray         # () float µJ actually consumed
    payload_bytes: jnp.ndarray # () float: 3 B early exit, 2 B full, else 0
    stored_uj: jnp.ndarray     # () post-slot supercap charge
    prev_label: jnp.ndarray    # () int32 AAC continuity after the slot
    emit: jnp.ndarray          # () int32: 0 none, 1 early exit, 2 full depth
    emit_label: jnp.ndarray    # () int32 (valid when emit > 0)
    emit_conf: jnp.ndarray     # () float aux-head max-softmax (early exits)
    emit_src: jnp.ndarray      # () int32 source slot of the emitted window
    emit_stage: jnp.ndarray    # () int32 depth at emission (1/2 early, 3 full)
    state: IntermittentState


def intermittent_lane_step(window: jnp.ndarray, state: SeekerNodeState,
                           harvested_uj: jnp.ndarray,
                           ladder_decision: jnp.ndarray,
                           it: IntermittentState, slot: jnp.ndarray, *,
                           qp: dict, aux_params: dict, har_cfg: HARConfig,
                           costs: EnergyCosts, quant_bits: int,
                           cfg: IntermittentConfig,
                           reserve_uj: float = 0.0,
                           cost_scale: jnp.ndarray | None = None
                           ) -> IntermittentLaneOut:
    """One slot of the energy-adaptive partial-inference lane (paper-adjacent
    intermittent computing: Islam et al. 2503.06663, Gobieski et al.
    1810.07751), for ONE node — the fleet engines vmap this after the ladder
    step.

    Engages when an inference is in flight (resume before starting new work)
    or when the ladder chose DEFER (the freeze-and-lose slot this lane
    converts into progress).  Under STRICT store-and-execute accounting —
    every µJ spent is gated on ``stored + harvested`` this slot, PR 5
    semantics, the forecast mints nothing — it:

    1. pays the sensing cost (zero-clamped exactly like strict DEFER),
    2. executes as many remaining stages as the budget affords
       (:meth:`repro.core.energy.EnergyCosts.stage_costs`), resuming from
       the suspended activation buffer,
    3. on full depth + an affordable ``tx_result``: emits D8,
    4. stalled with ``>= min_exit_stage`` stages done, an affordable
       ``aux_head + tx_result``, and aux confidence ``>= exit_threshold``:
       emits a confidence-tagged early exit, D7,
    5. otherwise suspends (D6 with progress in the carry; plain DEFER when
       nothing was started).

    ``qp`` is the PRE-quantized backbone (:func:`quantize_params` at
    ``quant_bits``) so the vmapped fleet quantizes once per slot, not per
    node.

    ``reserve_uj`` is the brown-out reserve: stage execution and emissions
    are additionally gated on leaving at least this much charge behind
    (the fleet engines pass ``BrownoutConfig.off_uj``).  Without it the
    lane spends every DEFER slot down to zero, tripping the power-down
    hysteresis and losing whole recharge cycles — threshold-aware
    budgeting is what makes staged progress a net win over freeze-and-
    lose (the benchmark's acceptance metric).  Sensing stays mandatory,
    exactly like strict DEFER.
    """
    sense = costs.sense
    tx = costs.tx_result
    aux_c = costs.aux_head
    stage_cost = costs.stage_costs(quant_bits)
    if cost_scale is not None:
        # heterogeneous-task lane: the whole staged ladder scales per node,
        # mirroring choose_decision's scaled D0-D4 table
        sense = sense * cost_scale
        tx = tx * cost_scale
        aux_c = aux_c * cost_scale
        stage_cost = jnp.asarray(stage_cost, jnp.float32) * cost_scale

    engaged = it.active | (ladder_decision == DEFER)
    budget = state.stored_uj + harvested_uj
    sense_ok = budget >= sense
    can_run = engaged & sense_ok
    spend = jnp.where(can_run, sense, 0.0)
    rem = budget - spend

    # resume-before-start: an in-flight inference owns the slot; otherwise
    # capture THIS slot's window as stage-0 input
    fresh = can_run & ~it.active
    a = it.acts.shape[0]
    win_flat = jnp.concatenate([
        window.reshape(-1),
        jnp.zeros((a - window.size,), jnp.float32)])
    buf = jnp.where(fresh, win_flat, it.acts)
    prog = jnp.where(fresh, 0, it.stage)
    src = jnp.where(fresh, slot, it.src_slot)

    # unrolled masked stage walk: each stage runs only if it is the next one
    # AND strictly affordable from what remains — no stage ever executes on
    # energy that does not exist
    for si in range(3):
        out_i = har_apply_stage(qp, buf, si, har_cfg, quant_bits)
        run_i = can_run & (prog == si) & (rem >= stage_cost[si] + reserve_uj)
        buf = jnp.where(run_i, out_i, buf)
        prog = jnp.where(run_i, prog + 1, prog)
        rem = jnp.where(run_i, rem - stage_cost[si], rem)
        spend = jnp.where(run_i, spend + stage_cost[si], spend)

    logits_full = buf[:har_cfg.n_classes]
    done = can_run & (prog == 3)
    emit_full = done & (rem >= tx + reserve_uj)

    aux_logits = har_apply_aux(aux_params, buf, prog, har_cfg, quant_bits)
    conf = jnp.max(jax.nn.softmax(aux_logits))
    emit_early = (can_run & ~done & (prog >= cfg.min_exit_stage)
                  & (rem >= aux_c + tx + reserve_uj)
                  & (conf >= cfg.exit_threshold))

    spend = spend + jnp.where(emit_full, tx, 0.0) \
        + jnp.where(emit_early, aux_c + tx, 0.0)
    emitted = emit_full | emit_early
    label = jnp.where(emit_full, jnp.argmax(logits_full),
                      jnp.argmax(aux_logits)).astype(jnp.int32)

    decision = jnp.where(
        emit_full, D8_STAGED_FULL,
        jnp.where(emit_early, D7_EARLY_EXIT,
                  jnp.where(can_run & (prog > 0), D6_PARTIAL,
                            DEFER))).astype(jnp.int32)
    # D7: 2-B result + 1-B confidence tag; D8: 2-B result
    payload = jnp.where(emit_full, 2.0, jnp.where(emit_early, 3.0, 0.0))
    stored = supercap_step_direct(state.stored_uj, harvested_uj, spend)
    prev_label = jnp.where(emitted, label, state.prev_label)

    new_it = IntermittentState(
        active=jnp.where(can_run, ~emitted & (prog > 0), it.active),
        stage=jnp.where(can_run, prog, it.stage),
        acts=buf,
        src_slot=src)
    return IntermittentLaneOut(
        engaged=engaged, decision=decision, spend=spend,
        payload_bytes=payload, stored_uj=stored, prev_label=prev_label,
        emit=jnp.where(emit_full, 2, jnp.where(emit_early, 1, 0)
                       ).astype(jnp.int32),
        emit_label=label, emit_conf=conf, emit_src=src,
        emit_stage=prog, state=new_it)


def seeker_simulate(windows: jnp.ndarray, labels: jnp.ndarray,
                    harvest: jnp.ndarray, *, signatures, qdnn_params,
                    host_params, gen_params, har_cfg: HARConfig,
                    aac_table: AACTable | None = None,
                    costs: EnergyCosts | None = None, n_sensors: int = 3,
                    key: jax.Array | None = None, quant_bits: int = 16,
                    brownout: BrownoutConfig | None = None,
                    intermittent: IntermittentConfig | None = None,
                    aux_params: dict | None = None):
    """Run the full Seeker system over a window stream.

    windows (S, T, C); harvest (S,) µJ per slot. The stream is replicated to
    ``n_sensors`` nodes with independent noise phases (sensor ensemble).
    Returns dict of traces: decisions, predictions, payload bytes, energy.

    Thin wrapper over :func:`repro.serving.fleet.seeker_fleet_simulate` with
    N = ``n_sensors`` replicated nodes — one fully batched scan instead of the
    per-sensor Python loop of :func:`seeker_simulate_reference`.

    ``brownout`` threads the fleet engine's endogenous brown-out lane
    through the single-node path: strict store-and-execute affordability and
    supercap-hysteresis churn (the returned dict gains per-slot ``alive`` /
    ``brownout`` lanes for sensor 0 plus the ``brownout_slots`` /
    ``brownout_events`` counters).  ``None`` is the legacy path, bitwise.

    ``intermittent`` (with ``aux_params``) threads the staged intermittent-
    inference lane the same way (see :func:`intermittent_lane_step`):
    DEFER slots become staged progress, and ``completed`` then counts
    everything but DEFER *and* D6 suspensions — a suspended inference put
    nothing on the wire yet.
    """
    from .fleet import seeker_fleet_simulate

    key = key if key is not None else jax.random.PRNGKey(0)
    s, t, c = windows.shape
    extra = ({} if intermittent is None else
             dict(intermittent=intermittent, aux_params=aux_params))
    fleet = seeker_fleet_simulate(
        windows, jnp.broadcast_to(harvest[None], (n_sensors, s)),
        signatures=signatures, qdnn_params=qdnn_params,
        host_params=host_params, gen_params=gen_params, har_cfg=har_cfg,
        aac_table=aac_table, costs=costs, key=key, quant_bits=quant_bits,
        brownout=brownout, **extra)
    # sensor ensemble (paper: host ensembles multiple sensors)
    ens_logits = jnp.mean(fleet["logits"], axis=1)           # (S, L)
    preds = jnp.argmax(ens_logits, axis=-1)
    completed = fleet["decisions"][:, 0] != DEFER
    if intermittent is not None:
        completed = completed & (fleet["decisions"][:, 0] != D6_PARTIAL)
    out = {
        "preds": preds,
        "labels": labels,
        "accuracy_completed": jnp.sum((preds == labels) & completed)
            / jnp.maximum(jnp.sum(completed), 1),
        "accuracy_scheduled": jnp.mean((preds == labels) & completed),
        "completed_frac": jnp.mean(completed.astype(jnp.float32)),
        "decisions": fleet["decisions"][:, 0],
        "payload_bytes": fleet["payload_bytes"][:, 0],
        "raw_bytes": float(raw_payload_bytes(t)) * jnp.ones((s,)),
        "stored_uj": fleet["stored_uj"][:, 0],
        "k_trace": fleet["k_trace"][:, 0],
        "alive": fleet["alive"][:, 0],
        "brownout": fleet["brownout"][:, 0],
        "brownout_slots": fleet["brownout_slots"],
        "brownout_events": fleet["brownout_events"],
    }
    if intermittent is not None:
        out.update({
            "it_emit": fleet["it_emit"][:, 0],
            "it_stage": fleet["it_stage"][:, 0],
            "it_full": fleet["it_full"],
            "it_early": fleet["it_early"],
        })
    return out


def seeker_simulate_reference(windows: jnp.ndarray, labels: jnp.ndarray,
                              harvest: jnp.ndarray, *, signatures,
                              qdnn_params, host_params, gen_params,
                              har_cfg: HARConfig,
                              aac_table: AACTable | None = None,
                              costs: EnergyCosts | None = None,
                              n_sensors: int = 3,
                              key: jax.Array | None = None,
                              quant_bits: int = 16):
    """Legacy per-sensor simulation: a Python loop of single-node scans.

    Kept as the semantics oracle for the fleet engine — tests assert
    :func:`seeker_fleet_simulate` reproduces these traces node for node.
    """
    costs = costs or EnergyCosts()
    key = key if key is not None else jax.random.PRNGKey(0)
    n, t, c = windows.shape

    def step(carry, inp):
        state, k = carry
        window, harvested = inp
        k, k1, k2 = jax.random.split(k, 3)
        out = seeker_sensor_step(
            window, state, harvested, signatures=signatures,
            qdnn_params=qdnn_params, har_cfg=har_cfg, aac_table=aac_table,
            costs=costs, key=k1, quant_bits=quant_bits)
        host_logits = seeker_host_step(out, host_params=host_params,
                                       gen_params=gen_params,
                                       har_cfg=har_cfg, key=k2, t=t)
        trace = {"decision": out.decision, "payload": out.payload_bytes,
                 "stored": out.state.stored_uj, "k": out.coreset_k,
                 "logits": host_logits}
        return (out.state, k), trace

    traces = []
    for sidx in range(n_sensors):
        init = (seeker_node_init(), jax.random.fold_in(key, sidx))
        _, tr = jax.lax.scan(step, init, (windows, harvest))
        traces.append(tr)
    # sensor ensemble (paper: host ensembles multiple sensors)
    ens_logits = sum(tr["logits"] for tr in traces) / n_sensors
    preds = jnp.argmax(ens_logits, axis=-1)
    completed = traces[0]["decision"] != DEFER
    return {
        "preds": preds,
        "labels": labels,
        "accuracy_completed": jnp.sum((preds == labels) & completed)
            / jnp.maximum(jnp.sum(completed), 1),
        "accuracy_scheduled": jnp.mean((preds == labels) & completed),
        "completed_frac": jnp.mean(completed.astype(jnp.float32)),
        "decisions": traces[0]["decision"],
        "payload_bytes": traces[0]["payload"],
        "raw_bytes": float(raw_payload_bytes(t)) * jnp.ones((n,)),
        "stored_uj": traces[0]["stored"],
        "k_trace": traces[0]["k"],
    }


# ---------------------------------------------------------------------------
# Coreset wire format (what actually crosses the pod axis)
# ---------------------------------------------------------------------------

class WirePayload(NamedTuple):
    """Quantized cluster-coreset payload as it crosses the wire: int16 center
    codes, int8 radius codes, int8 counts (modelling the paper's 2 B center /
    1 B radius / 4-bit count format, §3.2.2), plus the per-window float
    ranges needed to dequantize on the host side."""

    c_codes: jnp.ndarray    # (B, C, k, 2) int16
    r_codes: jnp.ndarray    # (B, C, k) int8
    n_codes: jnp.ndarray    # (B, C, k) int8
    lo: jnp.ndarray         # (B, 1, 1, 1) center range low
    hi: jnp.ndarray         # (B, 1, 1, 1) center range high
    rhi: jnp.ndarray        # (B, 1, 1) radius range high


def encode_wire_coresets(centers: jnp.ndarray, radii: jnp.ndarray,
                         counts: jnp.ndarray) -> WirePayload:
    """Quantize per-channel cluster coresets for transmission.

    centers (B, C, k, 2), radii (B, C, k), counts (B, C, k) — the batched
    output of :func:`repro.core.coreset.channel_cluster_coresets`.
    """
    lo = jnp.min(centers, axis=(1, 2, 3), keepdims=True)
    hi = jnp.max(centers, axis=(1, 2, 3), keepdims=True)
    c_codes = jnp.round((centers - lo) / jnp.maximum(hi - lo, 1e-9)
                        * 65535.0 - 32768.0).astype(jnp.int16)
    rhi = jnp.max(radii, axis=(1, 2), keepdims=True)
    r_codes = jnp.round(radii / jnp.maximum(rhi, 1e-9) * 255.0 - 128.0
                        ).astype(jnp.int8)
    n_codes = jnp.clip(counts, 0, 15).astype(jnp.int8)
    return WirePayload(c_codes, r_codes, n_codes, lo, hi, rhi)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(msg)


def _is_concrete(x) -> bool:
    """True when ``x`` carries actual values (not a jit/vmap tracer) — value
    validation only runs on the host ingest path, never during tracing."""
    return not isinstance(x, jax.core.Tracer)


def decode_wire_coresets(p: WirePayload):
    """Host-side dequantization; returns (centers, radii, counts int32).

    The host queue ingests these payloads from untrusted radio bytes, so the
    decode is defensive: field dtypes and cross-field shapes are validated
    always (static, jit-safe); code-range checks (4-bit counts) additionally
    run whenever the payload is concrete.  Malformed payloads raise
    ``ValueError`` instead of silently dequantizing garbage.
    """
    c_codes, r_codes, n_codes = map(jnp.asarray,
                                    (p.c_codes, p.r_codes, p.n_codes))
    _check(c_codes.dtype == jnp.int16,
           f"wire payload c_codes must be int16, got {c_codes.dtype}")
    _check(r_codes.dtype == jnp.int8,
           f"wire payload r_codes must be int8, got {r_codes.dtype}")
    _check(n_codes.dtype == jnp.int8,
           f"wire payload n_codes must be int8, got {n_codes.dtype}")
    _check(c_codes.ndim >= 2 and c_codes.shape[-1] == 2,
           f"wire payload c_codes must be (..., k, 2) 2-D center codes, "
           f"got shape {c_codes.shape}")
    _check(r_codes.shape == c_codes.shape[:-1],
           f"wire payload r_codes shape {r_codes.shape} does not match "
           f"c_codes {c_codes.shape}")
    _check(n_codes.shape == r_codes.shape,
           f"wire payload n_codes shape {n_codes.shape} does not match "
           f"r_codes {r_codes.shape}")
    for name, f in (("lo", p.lo), ("hi", p.hi), ("rhi", p.rhi)):
        _check(jnp.issubdtype(jnp.asarray(f).dtype, jnp.floating),
               f"wire payload {name} range must be floating, got "
               f"{jnp.asarray(f).dtype}")
    if _is_concrete(n_codes):
        import numpy as np
        nc = np.asarray(n_codes)
        _check(bool((nc >= 0).all() and (nc <= 15).all()),
               f"wire payload counts outside the 4-bit field [0, 15]: "
               f"min {nc.min()}, max {nc.max()}")

    centers = ((c_codes.astype(jnp.float32) + 32768.0) / 65535.0
               * (p.hi - p.lo) + p.lo)
    radii = (r_codes.astype(jnp.float32) + 128.0) / 255.0 * p.rhi
    return centers, radii, n_codes.astype(jnp.int32)


def wire_payload_nbytes(k: int, channels: int) -> int:
    """Bytes the quantized code tensors put on the wire per window (the
    collective_permute operand size, excluding the 3 float range scalars):
    per channel, k x (2-D int16 center + int8 radius + int8 count) — the
    paper's §3.2.2 accounting at the tensor field widths."""
    return channels * cluster_payload_bytes(k, bytes_center=4, bytes_radius=1,
                                            bits_count=8)


# --- byte-level framing: what the host's untrusted ingest actually parses --

_WIRE_MAGIC = 0x5EEC          # "SEEker Coreset"
_WIRE_VERSION = 1
_WIRE_HEADER = 20             # 5 x uint32: magic, version, B, C, k


def wire_payload_to_bytes(p: WirePayload) -> bytes:
    """Serialize a quantized coreset payload to one radio frame: a 20-B
    header (magic, version, B, C, k) followed by the little-endian code
    tensors and float ranges."""
    import numpy as np

    b, c, k, _ = p.c_codes.shape
    head = np.asarray([_WIRE_MAGIC, _WIRE_VERSION, b, c, k], "<u4")
    return b"".join([
        head.tobytes(),
        np.asarray(p.c_codes).astype("<i2").tobytes(),
        np.asarray(p.r_codes).astype("i1").tobytes(),
        np.asarray(p.n_codes).astype("i1").tobytes(),
        np.asarray(p.lo).astype("<f4").tobytes(),
        np.asarray(p.hi).astype("<f4").tobytes(),
        np.asarray(p.rhi).astype("<f4").tobytes(),
    ])


def wire_payload_from_bytes(buf: bytes) -> WirePayload:
    """Parse + validate one radio frame back into a :class:`WirePayload`.

    This is the host queue's trust boundary: buffer length, header fields,
    count codes and range floats are all checked, and any malformed frame
    raises ``ValueError`` with the reason — truncation, bad magic, counts
    outside the 4-bit field, or non-finite dequantization ranges.
    """
    import numpy as np

    buf = bytes(buf)
    _check(len(buf) >= _WIRE_HEADER,
           f"truncated wire frame: {len(buf)} B is shorter than the "
           f"{_WIRE_HEADER}-B header")
    magic, version, b, c, k = np.frombuffer(buf[:_WIRE_HEADER], "<u4")
    _check(magic == _WIRE_MAGIC,
           f"not a Seeker coreset frame (magic 0x{int(magic):X}, "
           f"want 0x{_WIRE_MAGIC:X})")
    _check(version == _WIRE_VERSION,
           f"unsupported wire version {int(version)} (want {_WIRE_VERSION})")
    b, c, k = int(b), int(c), int(k)
    _check(b > 0 and c > 0 and k > 0,
           f"degenerate wire dims B={b}, C={c}, k={k}")
    want = _WIRE_HEADER + 6 * b * c * k + 12 * b
    _check(len(buf) == want,
           f"truncated/oversized wire frame: {len(buf)} B, B={b} C={c} "
           f"k={k} needs {want} B")

    off = _WIRE_HEADER
    def take(count, dtype, shape):
        nonlocal off
        n = count * np.dtype(dtype).itemsize
        arr = np.frombuffer(buf[off:off + n], dtype).reshape(shape)
        off += n
        return arr

    c_codes = take(b * c * k * 2, "<i2", (b, c, k, 2))
    r_codes = take(b * c * k, "i1", (b, c, k))
    n_codes = take(b * c * k, "i1", (b, c, k))
    lo = take(b, "<f4", (b, 1, 1, 1))
    hi = take(b, "<f4", (b, 1, 1, 1))
    rhi = take(b, "<f4", (b, 1, 1))
    _check(bool((n_codes >= 0).all() and (n_codes <= 15).all()),
           f"wire frame counts outside the 4-bit field [0, 15]: "
           f"min {n_codes.min()}, max {n_codes.max()}")
    _check(bool(np.isfinite(lo).all() and np.isfinite(hi).all()
                and np.isfinite(rhi).all()),
           "wire frame dequantization ranges are not finite")
    _check(bool((hi >= lo).all()),
           "wire frame center range has hi < lo")
    return WirePayload(jnp.asarray(c_codes), jnp.asarray(r_codes),
                       jnp.asarray(n_codes), jnp.asarray(lo),
                       jnp.asarray(hi), jnp.asarray(rhi))


# ---------------------------------------------------------------------------
# Sampling-coreset wire format (the D4 payload: samples + GAN conditioning)
# ---------------------------------------------------------------------------

class WireSamplePayload(NamedTuple):
    """Quantized importance-sampling payload on the wire: int8 time indices
    (1 B, paper §3.2.2), int16 value codes (2 B per channel) with the
    per-window dequantization range, and the first/second moments that
    condition the recovery GAN (paper A.1) — carried as floats like the
    cluster format's range scalars, accounted at the paper's 2-B width."""

    idx: jnp.ndarray        # (B, m) int8 — selected time indices
    v_codes: jnp.ndarray    # (B, m, C) int16 — quantized sample values
    lo: jnp.ndarray         # (B, 1, 1) value range low
    hi: jnp.ndarray         # (B, 1, 1) value range high
    mean: jnp.ndarray       # (B, C) window mean (GAN conditioning)
    var: jnp.ndarray        # (B, C) window variance (GAN conditioning)


def encode_wire_samples(indices: jnp.ndarray, values: jnp.ndarray,
                        mean: jnp.ndarray, var: jnp.ndarray
                        ) -> WireSamplePayload:
    """Quantize batched sampling coresets for transmission.

    indices (B, m) int, values (B, m, C), mean/var (B, C) — the batched
    fields of :class:`repro.core.coreset.SamplingCoreset`.  Indices must fit
    the int8 wire field (window length < 128 — the paper's windows are 60).
    """
    if _is_concrete(indices):
        import numpy as np
        ix = np.asarray(indices)
        _check(bool((ix >= 0).all() and (ix <= 127).all()),
               f"sample indices outside the int8 wire field [0, 127]: "
               f"min {ix.min()}, max {ix.max()}")
    lo = jnp.min(values, axis=(1, 2), keepdims=True)
    hi = jnp.max(values, axis=(1, 2), keepdims=True)
    v_codes = jnp.round((values - lo) / jnp.maximum(hi - lo, 1e-9)
                        * 65535.0 - 32768.0).astype(jnp.int16)
    return WireSamplePayload(indices.astype(jnp.int8), v_codes, lo, hi,
                             mean.astype(jnp.float32),
                             var.astype(jnp.float32))


def decode_wire_samples(p: WireSamplePayload):
    """Host-side dequantization; returns (indices int32, values, mean, var).
    Defensive like :func:`decode_wire_coresets`: dtype/shape always checked,
    index-range checks when the payload is concrete."""
    idx, v_codes = jnp.asarray(p.idx), jnp.asarray(p.v_codes)
    _check(idx.dtype == jnp.int8,
           f"sample payload idx must be int8, got {idx.dtype}")
    _check(v_codes.dtype == jnp.int16,
           f"sample payload v_codes must be int16, got {v_codes.dtype}")
    _check(v_codes.ndim >= 1 and idx.shape == v_codes.shape[:-1],
           f"sample payload idx shape {idx.shape} does not match v_codes "
           f"{v_codes.shape}")
    mean, var = jnp.asarray(p.mean), jnp.asarray(p.var)
    _check(mean.shape[-1] == v_codes.shape[-1]
           and var.shape[-1] == v_codes.shape[-1],
           f"sample payload moments {mean.shape}/{var.shape} do not match "
           f"channel dim of v_codes {v_codes.shape}")
    if _is_concrete(idx):
        import numpy as np
        ix = np.asarray(idx)
        _check(bool((ix >= 0).all()),
               f"sample payload has negative time indices (min {ix.min()})")
    values = ((v_codes.astype(jnp.float32) + 32768.0) / 65535.0
              * (p.hi - p.lo) + p.lo)
    return idx.astype(jnp.int32), values, mean, var


def wire_sample_nbytes(m: int, channels: int) -> int:
    """Bytes a sampling payload puts on the wire per window: m x (1-B index
    + 2-B value per channel) + the 2-B mean/var moments per channel (paper
    §3.2.2 / A.1 accounting)."""
    return sampling_payload_bytes(m, channels=channels)


# ---------------------------------------------------------------------------
# Distributed edge-host step (pod-axis disaggregation, for the dry-run)
# ---------------------------------------------------------------------------

def _edge_encode_coresets(win: jnp.ndarray, k: int) -> WirePayload:
    """Edge half of a serving tier: per-channel cluster coresets for the
    LOCAL window batch (B, T, C), quantized to the wire format — the only
    tensors that ever cross the mesh."""
    centers, radii, counts = jax.vmap(
        lambda w: channel_cluster_coresets(w, k=k, iters=4))(win)
    return encode_wire_coresets(centers, radii, counts)


def edge_host_serve_step(windows: jnp.ndarray, *, signatures, qdnn_params,
                         host_params, gen_params, har_cfg: HARConfig,
                         mesh, k: int = 12, quant_bits: int = 16,
                         key: jax.Array | None = None):
    """Paired-tier serving across the "pod" mesh axis.

    Each pod is the *edge* for its own sensor batch (memoization + quantized
    DNN + cluster-coreset construction) and the *host* for its peer pod: the
    quantized coreset payload crosses pods via ``collective_permute`` —
    coreset bytes on the wire instead of raw windows (8.9x fewer, paper C3).

    windows: (B, T, C) globally, sharded over ("pod", "data") on B.
    Returns (B, n_classes) host logits for the *peer's* windows, in the peer
    pod's shards.

    The host half (decode -> batched recovery -> DNN) is the host-tier
    subsystem's :func:`repro.host.server.recover_infer_batch` — this
    function only models the *edge* side and the collective.
    """
    from jax.sharding import PartitionSpec as P

    from ..host.server import recover_infer_batch

    key = key if key is not None else jax.random.PRNGKey(0)
    t = windows.shape[1]

    def tier(win):
        # --- edge side: local sensors, quantized wire format (2B centers /
        # 1B radii / 4b counts modelled as int16/int8/int8 tensors: what
        # collective_permute actually moves) ---------------------------------
        payload = _edge_encode_coresets(win, k)

        # --- cross-pod transfer: coreset payload only ----------------------
        npods = jax.lax.psum(1, "pod")
        perm = [(i, (i + 1) % npods) for i in range(npods)]
        payload = WirePayload(*(jax.lax.ppermute(f, "pod", perm)
                                for f in payload))

        # --- host tier: recover the peer's coresets and infer ---------------
        return recover_infer_batch(
            payload, host_params,
            jax.random.split(key, payload.c_codes.shape[0]), t)

    from ..sharding import shard_map_compat
    fn = shard_map_compat(
        tier, mesh,
        in_specs=(P(("pod", "data")) if "pod" in mesh.shape else P("data"),),
        out_specs=P(("pod", "data")) if "pod" in mesh.shape else P("data"),
        axis_names=frozenset(a for a in ("pod", "data") if a in mesh.shape))
    return fn(windows)


def fleet_serve_step(windows: jnp.ndarray, *, host_params,
                     har_cfg: HARConfig, mesh, k: int = 12,
                     key: jax.Array | None = None,
                     host_state=None, serve_cfg=None, gen_params=None,
                     alive: jnp.ndarray | None = None,
                     engine_alive: jnp.ndarray | None = None,
                     per_shard_host: bool = False):
    """Sharded-fleet edge→host tier: gather ONLY coreset payloads to the host.

    The companion to :func:`repro.serving.fleet.seeker_fleet_simulate_sharded`
    for the offload decisions (D3): each shard builds per-channel cluster
    coresets for its *local* node tile and quantizes them to the compact wire
    format; the int16/int8 code tensors are then ``all_gather``-ed over the
    fleet's node axes (minor axis first, so global node order is preserved)
    to the host tier.  Raw windows and node state never leave their shard —
    only coreset bytes cross the mesh, reproducing the paper's edge-host
    communication asymmetry at the collective level.

    The host work is delegated to the host-tier subsystem (:mod:`repro.host`)
    in one of three modes:

    * default — the gathered batch runs straight through
      :func:`repro.host.server.recover_infer_batch` (decode -> batched
      recovery -> DNN), replicated, returning per-node logits;
    * ``host_state``/``serve_cfg`` given — the gathered payloads are
      *enqueued* into the host server (QoS deadline stamping, EDF microbatch
      assembly, recovery cache) and served at ``serve_cfg.batch_size``;
      returns the evolved ``host_state`` and the round's
      :class:`repro.host.server.SlotOutput` instead of raw logits, so a
      serving loop carries queue backlog / cache / ensemble across rounds.
      ``alive`` (the round's churn mask) keeps dead nodes' payloads out of
      the queue — a browned-out node produces no radio frame;
    * ``per_shard_host=True`` (with ``host_state``/``serve_cfg``) — the
      ROADMAP multi-host shape: NO gather at all.  Each shard runs its own
      host server (queue/EDF/cache) over the payloads of its local node
      tile; ``host_state`` must be the stacked per-shard carry from
      :func:`repro.host.server.host_server_init_stacked` (one server per
      shard, leading axis = the mesh quantum).  Only the QoS counters cross
      shards, psum'd into the returned ``qos`` dict — exactly how fleet
      aggregates cross shards in the simulator.

    Args:
        windows: (N, T, C) fleet sensor windows, one per node.  N that does
            not divide the mesh quantum is padded with zero windows and the
            padding is sliced off before the host tier sees it.
        mesh: mesh whose FLEET_RULES node axes carry the fleet.
        host_state: optional :class:`repro.host.server.HostServerState` to
            feed (requires ``serve_cfg`` and ``gen_params``); stacked
            per-shard when ``per_shard_host``.
        alive: optional (N,) bool — this round's *caller* churn mask (queue
            modes only): dead nodes' payloads never enqueue and transmit no
            wire bytes.
        engine_alive: optional (N,) bool — one slot of the fleet engine's
            EMITTED alive trace (``res["alive"][t]``), which already folds
            endogenous brown-outs into the exogenous trace.  Composes with
            ``alive`` by AND, so the host's per-round mask comes from the
            simulated physics, not just the caller: a node the engine
            browned out produces no radio frame either.  Queue modes only,
            like ``alive``.

    Returns dict: ``wire_bytes`` — total quantized payload bytes the alive
    fleet put on the wire, ``raw_bytes`` — the raw-window equivalent (the
    communication avoided), plus ``host_logits`` (N, L) (default mode) or
    ``host_state``/``slot_output`` (queue modes; per-shard mode adds the
    psum'd ``qos`` counter dict).
    """
    from ..host.server import recover_infer_batch, serve_fleet_payloads
    from ..sharding import node_mesh_axes, shard_map_compat

    key = key if key is not None else jax.random.PRNGKey(0)
    n, t, c = windows.shape
    axis_names, quantum = node_mesh_axes(mesh)
    if not axis_names:
        raise ValueError(
            f"mesh {mesh.axis_names} has none of the FLEET_RULES node axes")
    pad = (-n) % quantum
    if pad:
        windows = jnp.pad(windows, ((0, pad), (0, 0), (0, 0)))
    if engine_alive is not None:
        engine_alive = jnp.asarray(engine_alive, bool)
        if engine_alive.shape != (n,):
            raise ValueError(f"engine_alive must be (N,)=({n},), got "
                             f"{engine_alive.shape}")
        alive = engine_alive if alive is None else \
            jnp.asarray(alive, bool) & engine_alive
    if alive is not None:
        alive = jnp.asarray(alive, bool)
        if alive.shape != (n,):
            raise ValueError(f"alive must be (N,)=({n},), got {alive.shape}")
        if host_state is None:
            raise ValueError("alive/engine_alive is a queue-mode argument: "
                             "without a host_state there is no queue to "
                             "keep dead nodes out of")

    if per_shard_host:
        return _fleet_serve_per_shard(
            windows, n=n, t=t, c=c, k=k, mesh=mesh, axis_names=axis_names,
            quantum=quantum, host_params=host_params,
            host_state=host_state, serve_cfg=serve_cfg,
            gen_params=gen_params, alive=alive, key=key)

    def tier(win, kk):
        # --- edge side: coresets + wire quantization for LOCAL nodes only --
        payload = _edge_encode_coresets(win, k)

        # --- node axis -> host tier: the quantized codes are ALL that moves.
        # Gather the minor mesh axis first so the concatenated node order
        # matches the global (pod-major) layout of the padded fleet.
        for ax in reversed(axis_names):
            payload = WirePayload(*(jax.lax.all_gather(f, ax, axis=0,
                                                       tiled=True)
                                    for f in payload))

        if host_state is None:
            # --- host tier, direct mode: decode, recover, infer ------------
            return recover_infer_batch(
                payload, host_params,
                jax.random.split(kk, payload.c_codes.shape[0]), t)
            # -> (N+pad, L) replicated
        return payload               # -> gathered wire payload, replicated

    from jax.sharding import PartitionSpec as P
    out_specs = P() if host_state is None else WirePayload(*([P()] * 6))
    fn = shard_map_compat(tier, mesh, in_specs=(P(axis_names), P()),
                          out_specs=out_specs,
                          axis_names=frozenset(axis_names))
    n_tx = n if alive is None else int(jnp.sum(alive))   # frames transmitted
    out = {
        "wire_bytes": n_tx * wire_payload_nbytes(k, c),
        "raw_bytes": n * raw_payload_bytes(t) * c,
    }
    if host_state is None:
        out["host_logits"] = fn(windows, key)[:n]
        return out

    # --- queue mode: the gathered payloads FEED the host subsystem ---------
    if serve_cfg is None or gen_params is None:
        raise ValueError("fleet_serve_step host_state mode needs serve_cfg "
                         "and gen_params")
    payload = fn(windows, key)
    payload = WirePayload(*(f[:n] for f in payload))   # drop inert pad nodes
    state, slot_out = serve_fleet_payloads(
        host_state, payload, jnp.arange(n, dtype=jnp.int32), cfg=serve_cfg,
        host_params=host_params, gen_params=gen_params, base_key=key,
        mask=alive)
    out["host_state"] = state
    out["slot_output"] = slot_out
    return out


def _fleet_serve_per_shard(windows, *, n, t, c, k, mesh, axis_names,
                           quantum, host_params, host_state, serve_cfg,
                           gen_params, alive, key):
    """``fleet_serve_step``'s per-shard host mode (flag-gated).

    Each shard is its own host: local coreset encode feeds the shard's OWN
    queue/EDF/cache server — no payload gather, no replicated host work.
    The payload path is shard-local end to end; only the scalar QoS
    counters are psum'd (the multi-host QoS aggregation the ROADMAP names),
    so the collective footprint of a serve round drops from
    O(N · payload bytes) to O(1).
    """
    import dataclasses as _dc

    from jax.sharding import PartitionSpec as P

    from ..host.server import (SlotOutput, _slot_body, cluster_entries,
                               host_telemetry_spec)
    from ..obs import metrics_psum
    from ..sharding import shard_map_compat

    if serve_cfg is None or gen_params is None or host_state is None:
        raise ValueError("fleet_serve_step per_shard_host mode needs "
                         "host_state (stacked: host_server_init_stacked), "
                         "serve_cfg and gen_params")
    lead = jax.tree_util.tree_leaves(host_state)[0].shape[0]
    if lead != quantum:
        raise ValueError(
            f"per_shard_host needs one host server per shard: host_state "
            f"is stacked for {lead} hosts, mesh quantum is {quantum} "
            f"(use host_server_init_stacked(cfg, {quantum}))")
    n_pad = windows.shape[0]
    n_local = n_pad // quantum
    if n_local > serve_cfg.queue_capacity:
        raise ValueError(
            f"per-shard ingest lane of {n_local} nodes exceeds "
            f"queue_capacity={serve_cfg.queue_capacity}; raise "
            f"HostServeConfig.queue_capacity")
    # service rate: enough EDF microbatches to cover the LOCAL tile
    cfg = _dc.replace(serve_cfg,
                      batches_per_slot=-(-n_local // serve_cfg.batch_size))
    # pad nodes (global index >= n) and dead nodes never enqueue
    mask_full = jnp.arange(n_pad) < n
    if alive is not None:
        mask_full = mask_full & jnp.pad(alive, (0, n_pad - n))
    node_ids = jnp.arange(n_pad, dtype=jnp.int32)

    def tier(win, st_tile, nids, m, kk):
        # local edge encode -> the shard's own host server; nothing but the
        # psum'd QoS counters ever leaves the shard
        payload = _edge_encode_coresets(win, k)
        entries = cluster_entries(payload, cfg.m)
        state = jax.tree_util.tree_map(lambda a: a[0], st_tile)
        new_state, slot_out = _slot_body(
            cfg, state, entries, nids, m, host_params, gen_params, kk)
        qos = {
            name: jax.lax.psum(getattr(new_state, name), axis_names)
            for name in ("served", "deadline_misses")
        }
        qos["drops_overflow"] = jax.lax.psum(
            new_state.queue.drops_overflow, axis_names)
        if cfg.telemetry:
            # fleet-wide registry lanes: per-shard host lanes psum'd
            # component-wise, exactly like the fleet engine's — the
            # multi-host QoS-percentile substrate (histograms stay exact
            # int32 across any shard layout)
            qos["telemetry"] = metrics_psum(
                host_telemetry_spec(cfg), new_state.metrics, axis_names)
        return (jax.tree_util.tree_map(lambda a: a[None], new_state),
                slot_out, qos)

    nodes = P(axis_names)
    state_specs = jax.tree_util.tree_map(lambda _: nodes, host_state)
    qos_specs = {"served": P(), "deadline_misses": P(),
                 "drops_overflow": P()}
    if serve_cfg.telemetry:
        qos_specs["telemetry"] = {
            name: P() for name in host_telemetry_spec(serve_cfg).names()}
    fn = shard_map_compat(
        tier, mesh,
        in_specs=(nodes, state_specs, nodes, nodes, P()),
        out_specs=(state_specs,
                   SlotOutput(*([nodes] * len(SlotOutput._fields))),
                   qos_specs),
        axis_names=frozenset(axis_names))
    new_state, slot_out, qos = fn(windows, host_state, node_ids, mask_full,
                                  key)
    telemetry = qos.pop("telemetry", None)
    n_tx = n if alive is None else int(jnp.sum(alive))
    out = {
        "wire_bytes": n_tx * wire_payload_nbytes(k, c),
        "raw_bytes": n * raw_payload_bytes(t) * c,
        "host_state": new_state,
        "slot_output": slot_out,
        "qos": {k_: int(v) for k_, v in qos.items()},
    }
    if telemetry is not None:
        out["telemetry"] = telemetry
    return out
