"""Churn + streaming fleet engine, single-device (ISSUE 4).

The contracts pinned here (the sharded mirrors live in
tests/test_fleet_sharded.py's subprocess snippets):

* an all-True ``alive`` trace is BITWISE the churn-free engine;
* a dead slot freezes the node — supercapacitor charge, predictor history
  and the PRNG stream all hold, the node emits DEFER with zero payload, and
  on rejoin it continues exactly where it browned out;
* aggregates (decision histogram, completion, accuracy) count only alive
  slots — a browned-out node's forced DEFER is absence, not a decision;
* :func:`seeker_fleet_simulate_streamed` chunked runs are bitwise one long
  run, traces and final keys, churn and per-node labels included;
* the per-node-label accuracy contract: (S, N) tracks score each node
  against its OWN stream; a shared (S,) track with per-node streams raises
  (the silent bug this PR fixes).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.seeker_har import HAR
from repro.core import DEFER, fleet_alive_traces, fleet_harvest_traces, \
    fleet_phase_offsets
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.models.har import har_init
from repro.serving import (fleet_node_keys, seeker_fleet_simulate,
                           seeker_fleet_simulate_streamed)

S, N = 12, 4


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    params = har_init(key, HAR)
    gen = init_generator(key, HAR.window, HAR.channels)
    sigs = class_signatures()
    wins, labels = har_stream(key, S)
    harvest = fleet_harvest_traces(key, N, S)
    kw = dict(signatures=sigs, qdnn_params=params, host_params=params,
              gen_params=gen, har_cfg=HAR, key=key, donate=False)
    return key, wins, labels, harvest, kw


# ---------------------------------------------------------------------------
# Alive-trace generation
# ---------------------------------------------------------------------------

def test_alive_traces_shape_seeding_and_duty(key):
    tr = fleet_alive_traces(key, 6, 32, duty=0.5, period=8)
    assert tr.shape == (6, 32) and tr.dtype == bool
    # seeded like fleet_harvest_traces: reproducible, per-node folds
    np.testing.assert_array_equal(
        np.asarray(tr),
        np.asarray(fleet_alive_traces(key, 6, 32, duty=0.5, period=8)))
    # duty-cycled: every node both drops out and rejoins
    t = np.asarray(tr)
    assert ((~t).any(axis=1)).all() and (t.any(axis=1)).all()
    # phase offsets desynchronize nodes
    assert not all(np.array_equal(t[0], t[i]) for i in range(1, 6))
    phases = np.asarray(fleet_phase_offsets(key, 6, 8))
    assert phases.shape == (6,) and (phases >= 0).all() and (phases < 8).all()


def test_alive_traces_full_duty_is_all_true(key):
    tr = fleet_alive_traces(key, 4, 16, duty=1.0, p_glitch=0.0)
    assert bool(jnp.all(tr))


def test_alive_traces_bad_duty_raises(key):
    with pytest.raises(ValueError, match="duty"):
        fleet_alive_traces(key, 2, 4, duty=1.5)


# ---------------------------------------------------------------------------
# Churn equivalence + semantics
# ---------------------------------------------------------------------------

def test_all_true_alive_is_bitwise_churn_free(setup):
    """Acceptance: alive=ones == no alive argument, bit for bit (traces,
    aggregates, final state AND final PRNG keys)."""
    key, wins, labels, harvest, kw = setup
    base = seeker_fleet_simulate(wins, harvest, labels=labels, **kw)
    allT = seeker_fleet_simulate(wins, harvest, labels=labels,
                                 alive=jnp.ones((N, S), bool), **kw)
    for k in ("decisions", "payload_bytes", "stored_uj", "k_trace", "logits",
              "decision_histogram", "completed", "correct"):
        np.testing.assert_array_equal(np.asarray(base[k]),
                                      np.asarray(allT[k]), err_msg=k)
    np.testing.assert_array_equal(np.asarray(base["final_keys"]),
                                  np.asarray(allT["final_keys"]))
    np.testing.assert_array_equal(
        np.asarray(base["final_state"].stored_uj),
        np.asarray(allT["final_state"].stored_uj))
    assert int(allT["alive_slots"]) == S * N


def test_dead_slots_defer_zero_payload_frozen_state(setup):
    key, wins, labels, harvest, kw = setup
    alive = fleet_alive_traces(key, N, S, duty=0.5, period=4, p_glitch=0.1)
    res = seeker_fleet_simulate(wins, harvest, alive=alive, **kw)
    a = np.asarray(alive).T                                  # (S, N)
    assert a.sum() < S * N, "fixture must actually churn"
    dec = np.asarray(res["decisions"])
    assert (dec[~a] == DEFER).all()
    assert (np.asarray(res["payload_bytes"])[~a] == 0).all()
    assert (np.asarray(res["logits"])[~a] == 0).all()
    assert (np.asarray(res["k_trace"])[~a] == 0).all()
    # stored µJ holds its previous value through every dead slot
    stored = np.asarray(res["stored_uj"])
    for node in range(N):
        prev = 50.0
        for t in range(S):
            if not a[t, node]:
                assert stored[t, node] == prev, (t, node)
            prev = stored[t, node]


def test_always_dead_node_is_fully_inert(setup):
    """A node dead for the whole deployment neither consumes PRNG draws nor
    moves its state — and the other nodes are bitwise unaffected."""
    key, wins, labels, harvest, kw = setup
    alive = jnp.ones((N, S), bool).at[1].set(False)
    res = seeker_fleet_simulate(wins, harvest, alive=alive, **kw)
    base = seeker_fleet_simulate(wins, harvest, **kw)
    # node 1: untouched key and charge
    np.testing.assert_array_equal(
        np.asarray(res["final_keys"][1]),
        np.asarray(jax.random.fold_in(key, 1)))
    assert float(res["final_state"].stored_uj[1]) == 50.0
    assert (np.asarray(res["decisions"])[:, 1] == DEFER).all()
    # every other node: bitwise the churn-free trajectory
    keep = [0, 2, 3]
    for k in ("decisions", "payload_bytes", "stored_uj", "logits"):
        np.testing.assert_array_equal(np.asarray(res[k])[:, keep],
                                      np.asarray(base[k])[:, keep], err_msg=k)


def test_rejoin_continues_prng_stream(setup):
    """A node that sleeps through a PREFIX of the deployment wakes into
    exactly the trajectory of a fresh node at its rejoin charge: frozen
    slots consume no randomness (the PRNG lane is part of the freeze)."""
    key, wins, labels, harvest, kw = setup
    half = S // 2
    alive = jnp.ones((N, S), bool).at[0, :half].set(False)
    res = seeker_fleet_simulate(wins, harvest, alive=alive, **kw)
    # oracle: simulate only the tail, node 0 starting fresh at 50 µJ
    tail = seeker_fleet_simulate(wins[half:], harvest[:, half:], **kw)
    np.testing.assert_array_equal(np.asarray(res["decisions"])[half:, 0],
                                  np.asarray(tail["decisions"])[:, 0])
    np.testing.assert_array_equal(np.asarray(res["stored_uj"])[half:, 0],
                                  np.asarray(tail["stored_uj"])[:, 0])
    np.testing.assert_array_equal(np.asarray(res["final_keys"][0]),
                                  np.asarray(tail["final_keys"][0]))


def test_aggregates_respect_alive_mask(setup):
    key, wins, labels, harvest, kw = setup
    alive = fleet_alive_traces(key, N, S, duty=0.6, period=4)
    res = seeker_fleet_simulate(wins, harvest, alive=alive, labels=labels,
                                **kw)
    a = np.asarray(alive).T
    dec = np.asarray(res["decisions"])
    np.testing.assert_array_equal(
        np.asarray(res["decision_histogram"]),
        np.bincount(dec[a].ravel(), minlength=6))
    sent = (dec != DEFER) & a
    assert int(res["completed"]) == sent.sum()
    assert int(res["alive_slots"]) == a.sum()
    assert float(res["completed_frac"]) == pytest.approx(
        sent.sum() / max(a.sum(), 1), abs=1e-6)
    correct = ((np.asarray(res["preds"]) == np.asarray(labels)[:, None])
               & sent).sum()
    assert int(res["correct"]) == correct


def test_alive_wrong_shape_raises(setup):
    key, wins, labels, harvest, kw = setup
    with pytest.raises(ValueError, match="alive must be"):
        seeker_fleet_simulate(wins, harvest, alive=jnp.ones((N, S + 1), bool),
                              **kw)


# ---------------------------------------------------------------------------
# Per-node labels (the headline bugfix)
# ---------------------------------------------------------------------------

def test_shared_labels_with_per_node_streams_raise(setup):
    """The old engine silently scored every node's own stream against ONE
    label track; now it refuses."""
    key, wins, labels, harvest, kw = setup
    wn = jnp.stack([wins + 0.01 * i for i in range(N)])
    with pytest.raises(ValueError, match="ambiguous"):
        seeker_fleet_simulate(wn, harvest, labels=labels, **kw)


def test_labels_bad_shape_raises(setup):
    key, wins, labels, harvest, kw = setup
    with pytest.raises(ValueError, match="labels must be"):
        seeker_fleet_simulate(wins, harvest, labels=labels[: S - 1], **kw)


def test_swapped_label_tracks_regression(setup):
    """Two nodes playing each other's streams with correspondingly swapped
    (S, N) label tracks must score IDENTICALLY to the unswapped fleet — and
    NOT whatever comparing both nodes against track A would give (what the
    old shard body's ``preds == labels[:, None]`` did)."""
    key, wins, labels, harvest, kw = setup
    # stream B's seed is chosen so that the untrained host predicts some of
    # B's labels: with zero correct slots both scorings would count 0
    wins_b, labels_b = har_stream(jax.random.fold_in(key, 27), S)
    harvest2 = jnp.broadcast_to(harvest[:1], (2, S))   # same energy, 2 nodes

    streams = jnp.stack([wins, wins_b])                # node0=A, node1=B
    tracks = jnp.stack([labels, labels_b], axis=1)     # (S, 2)
    res = seeker_fleet_simulate(streams, harvest2, labels=tracks, **kw)

    # a node's PRNG stream is fold_in(key, position): swapping the nodes
    # swaps their keys too, so each stream keeps its own recovery draws
    swapped = seeker_fleet_simulate(
        streams[::-1], harvest2, labels=tracks[:, ::-1],
        node_keys=fleet_node_keys(key, 2)[::-1], **kw)
    # per-node scoring is permutation-equivariant: same counts either way
    assert int(res["correct"]) == int(swapped["correct"])
    assert int(res["completed"]) == int(swapped["completed"])

    # the OLD behaviour — both nodes scored against track A — differs:
    # recompute it from the traces and require the fixed engine NOT match it
    preds = np.asarray(res["preds"])
    sent = np.asarray(res["decisions"]) != DEFER
    old_correct = ((preds == np.asarray(labels)[:, None]) & sent).sum()
    new_correct = ((preds == np.asarray(tracks)) & sent).sum()
    assert int(res["correct"]) == new_correct
    assert new_correct != old_correct, \
        "fixture failed to distinguish the label tracks; change the seed"


# ---------------------------------------------------------------------------
# Streaming driver
# ---------------------------------------------------------------------------

def test_streamed_matches_one_long_run_bitwise(setup):
    """Acceptance: chunked segments through the resume contract == one long
    run, traces, counters and final keys, with churn + labels in play."""
    key, wins, labels, harvest, kw = setup
    alive = fleet_alive_traces(key, N, S, duty=0.7, period=4)
    full = seeker_fleet_simulate(wins, harvest, alive=alive, labels=labels,
                                 **kw)
    for chunk in (3, 5, S):          # divisible, ragged tail, single chunk
        stream = seeker_fleet_simulate_streamed(
            wins, harvest, chunk=chunk, alive=alive, labels=labels, **kw)
        for k in ("decisions", "payload_bytes", "stored_uj", "k_trace",
                  "logits", "preds"):
            np.testing.assert_array_equal(
                np.asarray(stream[k]), np.asarray(full[k]),
                err_msg=f"{k} (chunk={chunk})")
        np.testing.assert_array_equal(np.asarray(stream["final_keys"]),
                                      np.asarray(full["final_keys"]))
        np.testing.assert_array_equal(
            np.asarray(stream["final_state"].stored_uj),
            np.asarray(full["final_state"].stored_uj))
        for k in ("decision_histogram", "completed", "alive_slots",
                  "correct"):
            np.testing.assert_array_equal(np.asarray(stream[k]),
                                          np.asarray(full[k]), err_msg=k)
        assert stream["n_chunks"] == -(-S // chunk)
        np.testing.assert_allclose(float(stream["bytes_on_wire"]),
                                   float(full["bytes_on_wire"]), rtol=1e-6)


def test_streamed_accepts_window_callable(setup):
    """The point of streaming: the full (N, S, T, C) tensor never exists —
    a callable materializes one chunk at a time."""
    key, wins, labels, harvest, kw = setup
    wn = jnp.stack([wins + 0.01 * i for i in range(N)])   # (N, S, T, C)
    calls = []

    def window_fn(start, stop):
        calls.append((start, stop))
        return wn[:, start:stop]

    full = seeker_fleet_simulate(wn, harvest, **kw)
    stream = seeker_fleet_simulate_streamed(window_fn, harvest, chunk=4, **kw)
    assert calls == [(0, 4), (4, 8), (8, 12)]
    for k in ("decisions", "stored_uj", "logits"):
        np.testing.assert_array_equal(np.asarray(stream[k]),
                                      np.asarray(full[k]), err_msg=k)


def test_streamed_bad_chunk_raises(setup):
    key, wins, labels, harvest, kw = setup
    with pytest.raises(ValueError, match="chunk"):
        seeker_fleet_simulate_streamed(wins, harvest, chunk=0, **kw)
