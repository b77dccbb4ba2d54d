"""The comparison that decides ``correct``, run small on the CPU: a sound
run passes, the precision control fails, and each fault planted under the
timed path fails (the harness's look for a chip is skipped)."""
import jax
import pytest

from bench import common, compare, faults, run
from bench.drivers import fleet as fl

SIZES = {"config": {"n_nodes": 16},
         "traffic": {"segment_slots": 4, "horizon_slots": 8,
                     "sample_nodes": 16}}
CELLS = ("har_wearables.fleet", "bearing_plant.fleet")
SEED = 2 ** 33 + 1234
SECONDS = 0.2


def _limits(workload):
    return common.load_json(common.BENCH / "limits" / f"{workload}.json")


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = run.run_cell(workload, SEED, SECONDS, False, jax.devices()[:1],
                       SIZES)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 1
    assert set(out["checks"]) == set(_limits(workload))
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload, fault, check", [
    (cell, fault, check) for cell in CELLS for fault, check in (
        ("state_unchanged", None), ("half_batch", None),
        ("answer_altered", "logit_gap"), ("label_altered", "label_gap"))])
def test_planted_fault_is_caught(workload, fault, check):
    with faults.planted(fault):
        out = run.run_cell(workload, SEED, SECONDS, False, jax.devices()[:1],
                           SIZES)
    assert not out["correct"], out["checks"]
    if check:
        c = out["checks"][check]
        assert c["value"] > c["limit"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_precision_control_is_caught(workload):
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == workload)
    cfg = common.load_json(common.BENCH / "configs"
                           / f"{cell['config']}.json")
    traffic = common.load_json(common.BENCH / "traffic"
                               / f"{cell['traffic']}.json")
    cfg.update(SIZES["config"])
    traffic.update(SIZES["traffic"])
    fleet = fl.Fleet(cfg, traffic, SEED, jax.devices()[:1])
    ref = fl.reference_traces(fleet, 8)
    low = fl.reference_traces(fleet, 8, "high")
    ok, rows = compare.judge(compare.compare(low, ref), _limits(workload))
    assert not ok, rows
