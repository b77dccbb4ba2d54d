"""The host cell's comparison, run small on the CPU: a sound run passes,
the precision control fails, and each fault planted under the timed path
fails (the harness's look for a chip is skipped).

At 64 nodes the fleet's ladder sends few D3 and D4 payloads, so the small
run prices the on-node DNN and the cluster coreset out of reach: nearly
every alive node then sends a coreset, which keeps the small queue busy
enough to wrap its ring, back up and expire."""
import jax
import pytest

from bench import common, host_compare, host_faults, run
from bench.drivers import host as hd

WORKLOAD = "har_fleet_host.serve"
SEED = 2 ** 33 + 4321
SECONDS = 1.0


def _sizes():
    cell = common.load_json(common.BENCH / "configs" / "har_fleet_host.json")
    costs = dict(cell["costs"], dnn16=1000.0, dnn_full=1000.0,
                 coreset_cluster=8.0)
    return {"config": {"n_nodes": 64, "costs": costs,
                       "host": {"batch_size": 8, "batches_per_slot": 3,
                                "queue_capacity": 64, "cache_capacity": 16,
                                "qos_slots": 2, "telemetry": True}},
            "traffic": {"horizon_slots": 4, "compared_slots": 8}}


def _limits():
    return common.load_json(common.BENCH / "limits" / f"{WORKLOAD}.json")


def test_sound_run_is_correct():
    out = run.run_cell(WORKLOAD, SEED, SECONDS, False, jax.devices()[:1],
                       _sizes())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["checks"]) == set(_limits())
    assert set(out["metrics"]) == {"node_slots_per_s", "peak_hbm_gb",
                                   "setup_s"}


@pytest.mark.parametrize("fault", host_faults.FAULTS)
def test_planted_fault_is_caught(fault):
    with host_faults.planted(fault):
        out = run.run_cell(WORKLOAD, SEED, SECONDS, False,
                           jax.devices()[:1], _sizes())
    assert not out["correct"], out["checks"]


def test_precision_control_is_caught():
    sizes = _sizes()
    cfg = common.load_json(common.BENCH / "configs" / "har_fleet_host.json")
    traffic = common.load_json(common.BENCH / "traffic" / "serve.json")
    cfg.update(sizes["config"])
    traffic.update(sizes["traffic"])
    host = hd.Host(cfg, traffic, SEED, jax.devices()[:1])
    ref = hd.reference_slots(host, 8)
    low = hd.reference_slots(host, 8, "high")
    limits = _limits()
    ok, rows = host_compare.judge(
        host_compare.compare(low, ref, limits["logit_gap"]), limits)
    assert not ok, rows
