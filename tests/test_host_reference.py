"""The host tier against its plain reference (``bench/host_reference.py``)
at a small size, a fleet slot's ingest lane (``fleet_slot_entries``), the
fleet engine's coreset output, and the serve slot's batch loop (one
``lax.scan``, bitwise the unrolled form, a program that does not grow
with ``batches_per_slot``)."""
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

from bench import common, host_compare, host_reference, reference  # noqa: E402
from repro.configs.seeker_har import HAR  # noqa: E402
from repro.core import fleet_harvest_traces  # noqa: E402
from repro.core.coreset import (channel_cluster_coresets,  # noqa: E402
                                importance_coreset)
from repro.core.recovery import GeneratorParams  # noqa: E402
from repro.data.sensors import class_signatures, har_stream  # noqa: E402
from repro.host import (CLUSTER_KIND, SAMPLING_KIND,  # noqa: E402
                        HostServeConfig, cluster_entries, fleet_slot_entries,
                        host_serve_slot, host_server_init,
                        host_telemetry_spec, payload_signature,
                        sampling_entries)
from repro.host import server  # noqa: E402
from repro.obs import counter_add, gauge_set, hist_observe  # noqa: E402
from repro.host.queue import (queue_occupancy, queue_push_batch,  # noqa: E402
                              queue_wait_slots)
from repro.serving import (encode_wire_coresets,  # noqa: E402
                           encode_wire_samples, seeker_fleet_simulate)

N, K, M = 64, 12, 20
MODEL = {"window": HAR.window, "channels": HAR.channels,
         "n_classes": HAR.n_classes, "conv1": HAR.conv1, "conv2": HAR.conv2,
         "kernel": HAR.kernel, "hidden": HAR.hidden, "gen_hidden": 128}
HOST = {"batch_size": 8, "batches_per_slot": 3, "queue_capacity": 64,
        "qos_slots": 2, "cache_capacity": 16}
# at 24 payloads a slot, a 64-entry queue never holds more than a 2-slot
# deadline lets it serve; with a deadline of 1 slot the same trace expires
QOS = (2, 1)
D3, D4, DEFER = 3, 4, 5
# the host cell's logit limit
GAP = common.load_json(common.BENCH / "limits"
                       / "har_fleet_host.serve.json")["logit_gap"]


def _cfg(**kw):
    return HostServeConfig(channels=HAR.channels, k=K, m=M, t=HAR.window,
                           n_classes=HAR.n_classes, n_nodes=N,
                           **{**HOST, **kw})


def _coresets(key, windows):
    cs = jax.vmap(lambda w: channel_cluster_coresets(w, k=K, iters=4))(
        windows)
    keys = jax.random.split(key, windows.shape[0])
    sc = jax.vmap(lambda w, kk: importance_coreset(w, M, kk))(windows, keys)
    return {"centers": cs.centers, "radii": cs.radii, "counts": cs.counts,
            "samp_idx": sc.indices, "samp_vals": sc.values,
            "samp_mean": sc.mean, "samp_var": sc.var}


@pytest.fixture(scope="module")
def setup():
    """Seeded random weights and seven ingest lanes: a light slot, three
    heavy ones (backlog, then lanes that do not fit: the overflow path,
    and entries left to expire), a slot whose rows repeat three payloads
    (memo hits, batches served from the memo alone), a slot of nothing and
    a light one."""
    key = jax.random.PRNGKey(7)
    weights = reference.init_weights(key, MODEL)
    windows = jax.random.normal(jax.random.PRNGKey(8),
                                (N, HAR.window, HAR.channels))
    base = _coresets(jax.random.PRNGKey(9), windows)
    rng = np.random.default_rng(10)
    lanes = []
    for n_on, repeat in ((20, False), (40, False), (60, False), (60, False),
                         (48, True), (0, False), (30, False)):
        cs = base
        if repeat:
            # three distinct payloads, each on a third of the rows
            pick = jnp.asarray(np.arange(N) % 3)
            cs = jax.tree_util.tree_map(lambda a: a[pick], base)
        else:
            roll = int(rng.integers(N))
            cs = jax.tree_util.tree_map(lambda a: jnp.roll(a, roll, 0), base)
        dec = rng.choice([D3, D4, 2, DEFER], size=N, p=[0.45, 0.45, 0.05,
                                                        0.05])
        on = np.zeros(N, bool)
        on[rng.permutation(N)[:n_on]] = True
        dec = np.where(on, np.where(np.isin(dec, (D3, D4)), dec, D3), dec)
        alive = on | (rng.random(N) < 0.5)
        dec = np.where(on | ~alive, dec, DEFER)
        lanes.append(fleet_slot_entries(cs, jnp.asarray(dec),
                                        jnp.asarray(alive)))
    return weights, lanes, jax.random.PRNGKey(11)


def _serve(cfg, weights, lanes, key):
    cls, gen = weights
    state = host_server_init(cfg)
    slots = []
    for entries, nids, mask in lanes:
        state, out = host_serve_slot(state, entries, nids, mask, cfg=cfg,
                                     host_params=cls,
                                     gen_params=GeneratorParams(*gen),
                                     base_key=key)
        slots.append({
            "node_id": np.asarray(out.node_id),
            "deadline": np.asarray(out.deadline),
            "valid": np.asarray(out.valid),
            "cache_hit": np.asarray(out.cache_hit),
            "logits": np.asarray(out.logits),
            "votes": np.asarray(state.ensemble_votes),
            "counters": np.asarray([
                state.served, state.deadline_misses,
                state.queue.drops_overflow, queue_occupancy(state.queue),
                state.cache.hits, state.cache.misses])})
    return slots


def _plain(lanes):
    out = []
    for entries, nids, mask in lanes:
        lane = {k: np.asarray(v) for k, v in entries._asdict().items()}
        lane["node_id"], lane["mask"] = np.asarray(nids), np.asarray(mask)
        out.append(lane)
    return out


@pytest.fixture(scope="module")
def replayed(setup):
    """The reference's record of the trace, by deadline."""
    weights, lanes, key = setup
    return {qos: host_reference.replay(_plain(lanes), {**HOST,
                                                       "qos_slots": qos},
                                       N, weights, key, HAR.window,
                                       "highest") for qos in QOS}


@pytest.mark.parametrize("telemetry, qos", [(False, 2), (True, 2),
                                            (True, 1)])
def test_program_matches_reference(setup, replayed, telemetry, qos):
    weights, lanes, key = setup
    with jax.default_matmul_precision("highest"):
        prog = _serve(_cfg(telemetry=telemetry, qos_slots=qos), weights,
                      lanes, key)
    numbers = host_compare.compare(prog, replayed[qos], GAP)
    assert numbers["row_mismatch"] == 0, numbers
    assert numbers["counter_mismatch"] == 0, numbers
    assert numbers["vote_mismatch"] == 0, numbers
    assert numbers["logit_gap"] <= GAP, numbers
    assert numbers["rows"] > 100, numbers


def test_trace_exercises_every_path(setup, replayed):
    """Both kinds served, ties on one deadline, expiry, overflow drops, memo
    hits and a batch served from the memo alone."""
    weights, lanes, key = setup
    kinds = set()
    for entries, _, mask in lanes:
        k = np.asarray(entries.kind)[np.asarray(mask)]
        kinds |= set(k.tolist())
    assert kinds == {CLUSTER_KIND, SAMPLING_KIND}
    _, _, dropped, _, hits, _ = replayed[2][-1]["counters"]
    assert dropped > 0 and hits > 0
    assert replayed[1][-1]["counters"][1] > 0          # deadline misses
    first = replayed[2][0]
    assert len(set(first["deadline"][first["valid"]].tolist())) == 1
    b = HOST["batch_size"]
    assert any(slot["cache_hit"][i:i + b][slot["valid"][i:i + b]].all()
               and slot["valid"][i:i + b].any()
               for slot in replayed[2]
               for i in range(0, len(slot["valid"]), b))


def test_precision_control_is_caught(setup, replayed):
    weights, lanes, key = setup
    low = host_reference.replay(_plain(lanes), HOST, N, weights, key,
                                HAR.window, "high")
    ok, rows = host_compare.judge(host_compare.compare(low, replayed[2], GAP),
                                  {"logit_gap": GAP})
    assert not ok, rows


def test_reference_signature_is_the_programs(setup):
    _, lanes, _ = setup
    entries, _, _ = lanes[1]
    want = np.asarray(jax.vmap(payload_signature)(entries))
    got = host_reference.signatures(_plain(lanes[1:2])[0])
    np.testing.assert_array_equal(got, want)


def test_fleet_slot_entries_rows_are_the_wire_entries():
    windows = jax.random.normal(jax.random.PRNGKey(3), (6, HAR.window,
                                                        HAR.channels))
    cs = _coresets(jax.random.PRNGKey(4), windows)
    dec = jnp.asarray([D3, D4, 2, D3, D4, DEFER])
    alive = jnp.asarray([True, True, True, False, True, True])
    entries, nids, mask = fleet_slot_entries(cs, dec, alive)
    cl = cluster_entries(encode_wire_coresets(cs["centers"], cs["radii"],
                                              cs["counts"]), M)
    sa = sampling_entries(encode_wire_samples(
        cs["samp_idx"], cs["samp_vals"], cs["samp_mean"], cs["samp_var"]), K)
    for name, got in entries._asdict().items():
        got = np.asarray(got)
        np.testing.assert_array_equal(got[[0, 3]],
                                      np.asarray(getattr(cl, name))[[0, 3]])
        np.testing.assert_array_equal(got[[1, 4]],
                                      np.asarray(getattr(sa, name))[[1, 4]])
    np.testing.assert_array_equal(np.asarray(mask),
                                  [True, True, False, False, True, False])
    np.testing.assert_array_equal(np.asarray(nids), np.arange(6))


# ---------------------------------------------------------------------------
# The batch loop
# ---------------------------------------------------------------------------

def _unrolled_slot(cfg, state, entries, node_ids, mask, host_params,
                   gen_params, base_key):
    """The serve slot with its microbatches unrolled in Python, one copy
    of the batch body per microbatch: the form the scan replaced."""
    tel = host_telemetry_spec(cfg) if cfg.telemetry else None
    metrics = state.metrics
    arrival = jnp.broadcast_to(state.slot, node_ids.shape)
    queue, _ = queue_push_batch(state.queue, entries, node_ids, arrival,
                                arrival + cfg.qos_slots, mask)
    if tel is not None:
        metrics = counter_add(tel, metrics, "host.drops_overflow",
                              queue.drops_overflow
                              - state.queue.drops_overflow)
    carry = (queue, state.cache, state.served, state.deadline_misses,
             state.ensemble_logits, state.ensemble_votes, metrics)
    outs = []
    for _ in range(cfg.batches_per_slot):
        carry, out = server._serve_batch(cfg, tel, state.slot, carry,
                                         host_params, gen_params, base_key)
        outs.append(out)
    queue, cache, served, missed, ens_l, ens_v, metrics = carry
    if tel is not None:
        metrics = gauge_set(tel, metrics, "host.backlog",
                            queue_occupancy(queue))
        metrics = hist_observe(tel, metrics, "host.backlog_age_slots",
                               queue_wait_slots(queue, state.slot),
                               queue.valid)
    out = jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *outs)
    return server.HostServerState(queue, cache, state.slot + 1, served,
                                  missed, ens_l, ens_v, metrics), out


def test_batch_scan_is_bitwise_the_unrolled_form(setup):
    weights, lanes, key = setup
    cls, gen = weights
    gen = GeneratorParams(*gen)
    cfg = _cfg(telemetry=True)
    # the weights and key are arguments, as they are of the program's slot
    unrolled = jax.jit(functools.partial(_unrolled_slot, cfg))
    a = b = host_server_init(cfg)
    for entries, nids, mask in lanes:
        a, out_a = host_serve_slot(a, entries, nids, mask, cfg=cfg,
                                   host_params=cls, gen_params=gen,
                                   base_key=key)
        b, out_b = unrolled(b, entries, nids, mask, cls, gen, key)
        for x, y in zip(jax.tree_util.tree_leaves((a, out_a)),
                        jax.tree_util.tree_leaves((b, out_b))):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(x.reshape(-1).view(np.uint8),
                                          y.reshape(-1).view(np.uint8))


def test_slot_program_does_not_grow_with_batches(setup):
    weights, lanes, key = setup
    cls, gen = weights
    entries, nids, mask = lanes[0]

    def size(nb):
        cfg = _cfg(batches_per_slot=nb, telemetry=True)
        jaxpr = jax.make_jaxpr(lambda s: server._slot_body(
            cfg, s, entries, nids, mask, cls, GeneratorParams(*gen), key))(
            host_server_init(cfg))
        return len(str(jaxpr).splitlines())

    assert size(2) == size(9)


# ---------------------------------------------------------------------------
# The fleet engine's coreset output
# ---------------------------------------------------------------------------

def test_fleet_coreset_output_is_what_the_scan_sent():
    key = jax.random.PRNGKey(5)
    from repro.models.har import har_init
    from repro.core.recovery import init_generator
    params = har_init(key, HAR)
    gen = init_generator(key, HAR.window, HAR.channels)
    wins, _ = har_stream(key, 3)
    n = 4
    windows = jnp.broadcast_to(wins[None], (n,) + wins.shape)
    harvest = fleet_harvest_traces(key, n, 3)
    kw = dict(signatures=class_signatures(), qdnn_params=params,
              host_params=params, gen_params=gen, har_cfg=HAR, donate=False)
    off = seeker_fleet_simulate(windows, harvest, **kw)
    on = seeker_fleet_simulate(windows, harvest, coresets=True, **kw)
    assert "coresets" not in off
    for name in ("decisions", "payload_bytes", "stored_uj", "k_trace",
                 "logits", "alive"):
        np.testing.assert_array_equal(np.asarray(off[name]),
                                      np.asarray(on[name]))
    cs = on["coresets"]
    want = jax.vmap(jax.vmap(lambda w: channel_cluster_coresets(
        w, k=K, iters=4)))(jnp.moveaxis(windows, 0, 1))
    np.testing.assert_allclose(np.asarray(cs["centers"]),
                               np.asarray(want.centers), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(cs["counts"]),
                                  np.asarray(want.counts))
    assert cs["samp_idx"].shape == (3, n, M)
    assert cs["samp_vals"].shape == (3, n, M, HAR.channels)
    lane = fleet_slot_entries(jax.tree_util.tree_map(lambda a: a[0], cs),
                              on["decisions"][0], on["alive"][0])
    sent = np.asarray(on["alive"][0]) & np.isin(np.asarray(
        on["decisions"][0]), (D3, D4))
    np.testing.assert_array_equal(np.asarray(lane[2]), sent)

