"""Observability layer (ISSUE 8): registry exactness, engine telemetry
parity, host QoS percentiles, compile-shape budgets, the span tracer's
Chrome-trace export, and the benchmark regression gate.

The sharded half of the parity contract (lanes bitwise-equal across an
8-virtual-device mesh) lives in tests/test_fleet_sharded.py, which already
owns the subprocess mesh idiom.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.seeker_har import HAR
from repro.core import DEFER, BrownoutConfig, fleet_harvest_traces
from repro.core.coreset import channel_cluster_coresets, importance_coreset
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.host import (CLUSTER_KIND, HostServeConfig, cluster_entries,
                        host_serve_slot, host_server_init, host_server_stats,
                        host_telemetry_spec)
from repro.models.har import har_init
from repro.obs import (CompileBudgetError, MetricsSpec, categorical_counts,
                       compile_count, compile_event, compile_guard, counter,
                       counter_add, counter_value, gauge, gauge_set,
                       hist_observe, histogram, int_pair_sum, int_pair_total,
                       lane_edges, metrics_init, metrics_merge,
                       metrics_summary, percentile_from_hist, trace)
from repro.serving import (encode_wire_coresets, fleet_telemetry_spec,
                           seeker_fleet_simulate,
                           seeker_fleet_simulate_streamed, wire_bytes_exact)

S, N = 6, 5
ROOT_DIR = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Registry: exact int accounting on a jit-friendly pytree
# ---------------------------------------------------------------------------

def _spec():
    return MetricsSpec((counter("c", unit="B"), gauge("g"),
                        histogram("h_log", bins=6, lo=1.0, hi=100.0),
                        histogram("h_cat", bins=4, log=False)))


def test_counter_exact_past_float32_precision():
    """The reason counters are int32 pairs: float32 loses bytes past 2**24.
    Accumulate well beyond that and match an arbitrary-precision oracle."""
    spec = _spec()
    m = metrics_init(spec)
    vals = jnp.full((1000,), 2**21 + 7, jnp.int32)      # ~2.1e9 per round
    oracle = 0
    for _ in range(9):
        m = counter_add(spec, m, "c", vals)
        oracle += 1000 * (2**21 + 7)
    assert counter_value(m, "c") == oracle              # ~1.9e10 >> 2**24
    assert float(np.float32(oracle)) != oracle          # float32 would drift
    # the stored pair is canonical (lo digit < 2**16) — bitwise-comparable
    assert int(m["c"][1]) < 2**16


def test_counter_masks_bools_and_rounds_floats():
    spec = _spec()
    m = metrics_init(spec)
    m = counter_add(spec, m, "c", jnp.asarray([3.0, 4.0, 100.0]),
                    mask=jnp.asarray([True, True, False]))
    m = counter_add(spec, m, "c", jnp.asarray([True, False, True]))
    assert counter_value(m, "c") == 7 + 2
    pair = int_pair_sum(jnp.asarray([70000, 70000]))    # digit-split is exact
    assert int_pair_total(pair) == 140000


def test_gauge_latest_wins_and_kind_checks():
    spec = _spec()
    m = metrics_init(spec)
    m = gauge_set(spec, m, "g", jnp.asarray(41))
    m = gauge_set(spec, m, "g", jnp.asarray(17))
    assert int(m["g"]) == 17
    with pytest.raises(ValueError, match="not a counter"):
        counter_add(spec, m, "g", jnp.asarray([1]))
    with pytest.raises(ValueError, match="not a gauge"):
        gauge_set(spec, m, "c", jnp.asarray(1))
    with pytest.raises(ValueError, match="not a histogram"):
        hist_observe(spec, m, "c", jnp.asarray([1.0]))
    with pytest.raises(KeyError, match="no lane"):
        spec.lane("nope")
    with pytest.raises(ValueError, match="duplicate lane"):
        MetricsSpec((counter("x"), gauge("x")))


def test_histogram_binning_log_and_categorical():
    spec = _spec()
    m = metrics_init(spec)
    # log lane: v <= lo -> bin 0, v > hi -> overflow bin (the last)
    m = hist_observe(spec, m, "h_log",
                     jnp.asarray([0.5, 1.0, 5.0, 99.0, 1e6]))
    counts = np.asarray(m["h_log"])
    assert counts.sum() == 5
    assert counts[0] == 2 and counts[-1] == 1
    # categorical lane: integer k lands in bin k, clipped into the last
    m = hist_observe(spec, m, "h_cat", jnp.asarray([0, 1, 1, 3, 9]),
                     mask=jnp.asarray([1, 1, 1, 1, 0]))
    np.testing.assert_array_equal(np.asarray(m["h_cat"]), [1, 2, 0, 1])
    assert lane_edges(spec.lane("h_cat")) == (0.5, 1.5, 2.5)


def test_categorical_counts_matches_bincount():
    rng = np.random.RandomState(0)
    codes = rng.randint(0, 6, size=(7, 11))
    mask = rng.rand(7, 11) < 0.6
    got = np.asarray(categorical_counts(jnp.asarray(codes), 6,
                                        jnp.asarray(mask)))
    np.testing.assert_array_equal(got,
                                  np.bincount(codes[mask], minlength=6))


def test_percentile_from_hist_interpolates():
    # 4 obs in [0, 1], 8 in (1, 2]: p50 target is 6 obs -> 1/4 into bin 1
    edges = [1.0, 2.0, 3.0]
    assert percentile_from_hist([4, 8, 0, 0], edges, 50.0) \
        == pytest.approx(1.25)
    assert percentile_from_hist([4, 8, 0, 0], edges, 100.0) \
        == pytest.approx(2.0)
    # overflow bin reports its lower edge ("at least hi")
    assert percentile_from_hist([0, 0, 0, 5], edges, 50.0) == 3.0
    assert np.isnan(percentile_from_hist([0, 0, 0, 0], edges, 50.0))


def test_merge_chain_equals_single_pass():
    """The streamed resume rule: merging per-segment metrics is bitwise the
    one-long-run lane state."""
    spec = _spec()
    rng = np.random.RandomState(3)
    segs = []
    one = metrics_init(spec)
    for i in range(3):
        m = metrics_init(spec)
        vals = jnp.asarray(rng.randint(0, 10**6, size=16))
        hv = jnp.asarray(rng.uniform(0.5, 200.0, size=16))
        m = counter_add(spec, m, "c", vals)
        m = gauge_set(spec, m, "g", jnp.asarray(i))
        m = hist_observe(spec, m, "h_log", hv)
        one = counter_add(spec, one, "c", vals)
        one = gauge_set(spec, one, "g", jnp.asarray(i))
        one = hist_observe(spec, one, "h_log", hv)
        segs.append(m)
    merged = None
    for m in segs:
        merged = metrics_merge(spec, merged, m)
    for name in spec.names():
        np.testing.assert_array_equal(np.asarray(merged[name]),
                                      np.asarray(one[name]), err_msg=name)
    summ = metrics_summary(spec, merged)
    assert summ["c"] == counter_value(one, "c") and summ["g"] == 2
    assert set(summ["h_log"]) == {"counts", "edges", "unit",
                                  "p50", "p95", "p99"}


# ---------------------------------------------------------------------------
# Fleet engine telemetry: off = bitwise-identical, on = lanes match the
# engine's own aggregates, streamed chain = one long run
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_setup():
    key = jax.random.PRNGKey(0)
    params = har_init(key, HAR)
    gen = init_generator(key, HAR.window, HAR.channels)
    wins, labels = har_stream(key, S)
    harvest = fleet_harvest_traces(key, N, S)
    kw = dict(signatures=class_signatures(), qdnn_params=params,
              host_params=params, gen_params=gen, har_cfg=HAR,
              labels=labels, donate=False)
    return key, wins, harvest, kw


def test_fleet_telemetry_none_is_bitwise_identical(fleet_setup):
    key, wins, harvest, kw = fleet_setup
    off = seeker_fleet_simulate(wins, harvest, **kw)
    on = seeker_fleet_simulate(wins, harvest, telemetry=True, **kw)
    assert "telemetry" not in off
    for k in ("decisions", "payload_bytes", "stored_uj", "logits", "preds"):
        np.testing.assert_array_equal(np.asarray(on[k]), np.asarray(off[k]),
                                      err_msg=k)


def test_fleet_lanes_match_engine_aggregates(fleet_setup):
    key, wins, harvest, kw = fleet_setup
    res = seeker_fleet_simulate(wins, harvest, telemetry=True,
                                brownout=BrownoutConfig(off_uj=8.0,
                                                        restart_uj=28.0),
                                initial_uj=10.0, **kw)
    tel, spec = res["telemetry"], res["telemetry_spec"]
    assert spec is fleet_telemetry_spec(False)
    assert counter_value(tel, "fleet.wire_bytes") == wire_bytes_exact(res)
    assert counter_value(tel, "fleet.completed") == int(res["completed"])
    assert counter_value(tel, "fleet.alive_slots") == int(res["alive_slots"])
    assert counter_value(tel, "fleet.brownout_slots") \
        == int(res["brownout_slots"])
    assert counter_value(tel, "fleet.brownout_events") \
        == int(res["brownout_events"])
    np.testing.assert_array_equal(np.asarray(tel["fleet.decisions"]),
                                  np.asarray(res["decision_histogram"]))
    # gauge: the last slot's total stored charge over alive nodes
    last_alive = np.asarray(res["alive"])[-1]
    want = int(np.floor(np.asarray(res["stored_uj"])[-1])[last_alive].sum())
    assert int(tel["fleet.stored_uj"]) == want
    # non-DEFER alive slots == the completed counter (no intermittent lane)
    dec = np.asarray(res["decisions"])
    sent = (dec != DEFER) & np.asarray(res["alive"])
    assert counter_value(tel, "fleet.completed") == sent.sum()


def test_fleet_streamed_lanes_equal_one_long_run(fleet_setup):
    key, wins, harvest, kw = fleet_setup
    one = seeker_fleet_simulate(wins, harvest, telemetry=True, **kw)
    chunked = seeker_fleet_simulate_streamed(wins, harvest, chunk=4,
                                             telemetry=True, **kw)
    assert chunked["n_chunks"] == 2
    spec = one["telemetry_spec"]
    for name in spec.names():
        np.testing.assert_array_equal(
            np.asarray(chunked["telemetry"][name]),
            np.asarray(one["telemetry"][name]), err_msg=name)


def test_fleet_compile_budget_under_churny_aliveness(fleet_setup):
    """The generalized serve_trace_count contract on the fleet engine: alive
    masks that churn per run never change a tensor shape, so the engine
    stays within a 2-compiled-shape budget across repeated runs."""
    key, wins, harvest, kw = fleet_setup
    rng = np.random.RandomState(5)
    with compile_guard("fleet.run", 2):
        for _ in range(3):
            alive = jnp.asarray(rng.rand(N, S) < 0.7)
            seeker_fleet_simulate(wins, harvest, alive=alive,
                                  telemetry=True, **kw)


# ---------------------------------------------------------------------------
# Host telemetry: QoS percentiles, exactness, off = bitwise-identical
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def host_setup():
    key = jax.random.PRNGKey(0)
    params = har_init(key, HAR)
    gen = init_generator(key, HAR.window, HAR.channels)
    wins, _ = har_stream(key, 8)
    centers, radii, counts = jax.vmap(
        lambda w: channel_cluster_coresets(w, k=12, iters=4))(wins)
    wire = encode_wire_coresets(centers, radii, counts)
    return key, params, gen, wire


def _host_cfg(**kw):
    base = dict(channels=HAR.channels, k=12, m=20, t=HAR.window,
                n_classes=HAR.n_classes, n_nodes=8, batch_size=4,
                queue_capacity=16, cache_capacity=16, qos_slots=4)
    base.update(kw)
    return HostServeConfig(**base)


def _serve_slots(cfg, wire, key, params, gen, n_slots=3):
    entries = cluster_entries(wire, cfg.m)
    nid = jnp.arange(8, dtype=jnp.int32)
    mask = jnp.ones((8,), bool)
    state = host_server_init(cfg)
    outs = []
    for _ in range(n_slots):
        state, out = host_serve_slot(state, entries, nid, mask, cfg=cfg,
                                     host_params=params, gen_params=gen,
                                     base_key=key)
        outs.append(out)
    return state, outs


def test_host_telemetry_off_is_bitwise_identical(host_setup):
    key, params, gen, wire = host_setup
    _, off = _serve_slots(_host_cfg(), wire, key, params, gen)
    _, on = _serve_slots(_host_cfg(telemetry=True), wire, key, params, gen)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(np.asarray(a.logits),
                                      np.asarray(b.logits))
        np.testing.assert_array_equal(np.asarray(a.valid),
                                      np.asarray(b.valid))


def test_host_lanes_match_stats_and_percentiles(host_setup):
    key, params, gen, wire = host_setup
    cfg = _host_cfg(telemetry=True)
    state, _ = _serve_slots(cfg, wire, key, params, gen)
    stats = host_server_stats(state, cfg)
    tel = stats["telemetry"]
    assert tel["host.served"] == stats["served"] == 12
    assert tel["host.cache_hits"] == stats["cache_hits"]
    assert tel["host.cache_misses"] == stats["cache_misses"]
    assert tel["host.deadline_misses"] == stats["deadline_misses"]
    assert tel["host.drops_overflow"] == stats["drops_overflow"]
    assert tel["host.backlog"] == stats["backlog"]
    # every served payload's sojourn was recorded, all of them cluster-kind
    soj = tel["host.sojourn_slots"]
    assert sum(soj["counts"]) == stats["served"]
    assert sum(tel["host.sojourn_slots.cluster"]["counts"]) \
        == stats["served"]
    assert sum(tel["host.sojourn_slots.sampling"]["counts"]) == 0
    # percentiles: flattened floats, e2e = sojourn + the serve slot itself
    for k in ("sojourn_p50", "sojourn_p95", "sojourn_p99",
              "e2e_p50", "e2e_p95", "e2e_p99"):
        assert isinstance(stats[k], float) and stats[k] >= 0.0
    assert stats["e2e_p50"] >= stats["sojourn_p50"]
    assert stats["sojourn_p99"] <= cfg.qos_slots + 1
    assert CLUSTER_KIND == 0  # the kind code the per-class lanes split on


def test_host_spec_shared_across_service_rate_variants():
    """The lane spec depends only on the QoS window, so the per-slot and
    trace-mode configs of one deployment share a spec instance (one compile
    cache key, mergeable lanes)."""
    a = host_telemetry_spec(_host_cfg(telemetry=True))
    b = host_telemetry_spec(_host_cfg(telemetry=True, batch_size=8,
                                      queue_capacity=32))
    assert a is b
    assert host_telemetry_spec(_host_cfg(telemetry=True, qos_slots=9)) \
        is not a


def test_host_state_telemetry_mismatch_raises(host_setup):
    key, params, gen, wire = host_setup
    cfg = _host_cfg(telemetry=True)
    stale = host_server_init(_host_cfg())            # built without lanes
    entries = cluster_entries(wire, cfg.m)
    nid = jnp.arange(8, dtype=jnp.int32)
    with pytest.raises(ValueError, match="SAME telemetry"):
        host_serve_slot(stale, entries, nid, jnp.ones((8,), bool), cfg=cfg,
                        host_params=params, gen_params=gen, base_key=key)


# ---------------------------------------------------------------------------
# Compile guard + span tracer
# ---------------------------------------------------------------------------

def test_compile_guard_budget_raises():
    compile_event("obs.test_component", ("shape", 1))
    before = compile_count("obs.test_component")
    with compile_guard("obs.test_component", 2):
        compile_event("obs.test_component", ("shape", 2))
    assert compile_count("obs.test_component") == before + 1
    with pytest.raises(CompileBudgetError, match="budget of 1"):
        with compile_guard("obs.test_component", 1):
            for i in range(3):
                compile_event("obs.test_component", ("churn", i))


def test_trace_export_is_chrome_trace_json(tmp_path):
    was = trace.enabled()
    trace.clear()
    try:
        with trace.span("off.span"):                 # disabled: no event
            pass
        assert trace.events() == []
        trace.enable()
        with trace.span("work", cat="test", args={"n": 3},
                        flush=jnp.arange(4)):
            trace.instant("retrace", cat="test")
        path = tmp_path / "trace.json"
        assert trace.export_chrome_trace(str(path)) == 2
    finally:
        trace.enable(was)
        trace.clear()
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and len(evs) == 2
    kinds = {e["ph"]: e for e in evs}
    assert kinds["i"]["name"] == "retrace"
    sp = kinds["X"]
    assert sp["name"] == "work" and sp["args"] == {"n": 3}
    assert sp["dur"] >= 0 and {"ts", "pid", "tid"} <= sp.keys()
    # the instant fired inside the span's interval
    assert sp["ts"] <= kinds["i"]["ts"] <= sp["ts"] + sp["dur"]


# ---------------------------------------------------------------------------
# Layer scopes and profiler spans
# ---------------------------------------------------------------------------

ONE_CHIP_SCOPES = sorted(set(trace.SCOPES) - {"seeker.collectives"})


@pytest.fixture(scope="module")
def fleet_hlo():
    """The compiled one-chip fleet engine at N=4, S=2."""
    key = jax.random.PRNGKey(1)
    params = har_init(key, HAR)
    gen = init_generator(key, HAR.window, HAR.channels)
    wins, _ = har_stream(key, 2)
    harvest = fleet_harvest_traces(key, 4, 2)
    sigs = class_signatures()
    run = jax.jit(lambda w, h: seeker_fleet_simulate(
        w, h, signatures=sigs, qdnn_params=params, host_params=params,
        gen_params=gen, har_cfg=HAR, donate=False))
    return run.lower(wins, harvest).compile().as_text()


@pytest.mark.parametrize("name", ONE_CHIP_SCOPES)
def test_fleet_layer_scope_in_op_metadata(fleet_hlo, name):
    op_names = re.findall(r'op_name="([^"]*)"', fleet_hlo)
    assert any(re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])", o)
               for o in op_names), name


@pytest.fixture(scope="module")
def host_hlo(host_setup):
    """The compiled host serve slot (telemetry on, two microbatches) on a
    lane of both payload kinds."""
    from repro.host import sampling_entries
    from repro.host.server import _build_serve_slot
    from repro.serving import encode_wire_samples
    key, params, gen, wire = host_setup
    cfg = _host_cfg(telemetry=True, batches_per_slot=2)
    wins, _ = har_stream(key, 8)
    sc = jax.vmap(lambda w, kk: importance_coreset(w, cfg.m, kk))(
        wins, jax.random.split(key, 8))
    swire = encode_wire_samples(sc.indices, sc.values, sc.mean, sc.var)
    entries = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a[:4], b[:4]]),
        cluster_entries(wire, cfg.m), sampling_entries(swire, cfg.k))
    run = _build_serve_slot(cfg, False)
    return run.lower(host_server_init(cfg), entries,
                     jnp.arange(8, dtype=jnp.int32), jnp.ones((8,), bool),
                     params, gen, key).compile().as_text()


@pytest.mark.parametrize("name", sorted(trace.HOST_SCOPES))
def test_host_layer_scope_in_op_metadata(host_hlo, name):
    op_names = re.findall(r'op_name="([^"]*)"', host_hlo)
    assert any(re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])", o)
               for o in op_names), name


def test_scope_refuses_unregistered_name():
    with pytest.raises(ValueError, match="not registered"):
        trace.scope("seeker.unregistered")
    with trace.scope("seeker.corr"):
        pass


def test_scope_names_in_use_are_registered():
    """Every ``scope(...)`` call of the program names a registered scope,
    every registered scope is used, and every scope name the benchmark's
    code mentions is registered."""
    used = set()
    for path in (ROOT_DIR / "src" / "repro").rglob("*.py"):
        used |= set(re.findall(r'\bscope\("([^"]+)"\)', path.read_text()))
    assert used == set(trace.SCOPES) | set(trace.HOST_SCOPES)
    named = set()
    for path in (ROOT_DIR / "bench").rglob("*.py"):
        named |= set(re.findall(r'"(seeker\.[A-Za-z_]+)"',
                                path.read_text()))
    assert named <= set(trace.SCOPES)


def test_span_lands_on_profiler_host_plane(tmp_path):
    """A span is a profiler annotation whether or not the wall-clock buffer
    is on; with the buffer off it records no Chrome event."""
    was = trace.enabled()
    trace.enable(False)
    trace.clear()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace.span("obs.profiled_span"):
                jnp.arange(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        assert trace.events() == []
    finally:
        trace.enable(was)
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    host = [e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    assert "obs.profiled_span" in host


# ---------------------------------------------------------------------------
# Benchmark regression gate
# ---------------------------------------------------------------------------

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _load_compare():
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(ROOT, "benchmarks", "compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compare_detects_injected_regressions():
    cmp = _load_compare()
    base = {"x": {"name": "x", "completed_frac": 0.5, "us_per_call": 100.0,
                  "windows_per_s": 1000.0, "bitwise_equal": True},
            "y": {"name": "y", "reduction_x": 30.0}}
    ok = {k: dict(v) for k, v in base.items()}
    assert cmp.compare(ok, base, rtol=1e-6, timing_rtol=0.5) == []
    # deterministic drift beyond rtol -> regression
    bad = {k: dict(v) for k, v in base.items()}
    bad["x"]["completed_frac"] = 0.4
    assert any("completed_frac" in p
               for p in cmp.compare(bad, base, 1e-6, 0.5))
    # timing: 10x slower fails, 10x faster passes
    slow = {k: dict(v) for k, v in base.items()}
    slow["x"]["us_per_call"] = 1000.0
    assert any("us_per_call" in p for p in cmp.compare(slow, base, 1e-6, 0.5))
    fast = {k: dict(v) for k, v in base.items()}
    fast["x"]["us_per_call"] = 10.0
    fast["x"]["windows_per_s"] = 10000.0
    assert cmp.compare(fast, base, 1e-6, 0.5) == []
    # a vanished benchmark row is a regression; a flipped bool too
    missing = {"x": dict(base["x"])}
    assert any("missing" in p for p in cmp.compare(missing, base, 1e-6, 0.5))
    flipped = {k: dict(v) for k, v in base.items()}
    flipped["x"]["bitwise_equal"] = False
    assert any("bitwise_equal" in p
               for p in cmp.compare(flipped, base, 1e-6, 0.5))


def test_compare_cli_exit_codes(tmp_path):
    rows = [{"name": "m", "completed_frac": 0.75}]
    base = tmp_path / "base.json"
    base.write_text(json.dumps(rows))
    cur_ok = tmp_path / "ok.json"
    cur_ok.write_text(json.dumps(rows))
    cur_bad = tmp_path / "bad.json"
    cur_bad.write_text(json.dumps([{"name": "m", "completed_frac": 0.25}]))
    cmd = [sys.executable, "-m", "benchmarks.compare"]
    env = dict(os.environ)
    ok = subprocess.run(cmd + [str(cur_ok), "--baseline", str(base)],
                        cwd=ROOT, env=env, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(cmd + [str(cur_bad), "--baseline", str(base)],
                         cwd=ROOT, env=env, capture_output=True, text=True)
    assert bad.returncode != 0
    assert "REGRESSION" in bad.stdout
