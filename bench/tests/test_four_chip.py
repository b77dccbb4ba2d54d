"""The fleet driver on the sharded engine (the ``har_wearables_x4``
configuration, 4 chips), run small on four virtual CPU devices in a child
process (the tests' own process keeps one device): a sound run passes,
and leaving out the exchange between chips fails."""
import json
import os
import subprocess
import sys

import pytest

from bench import common

SIZES = {"config": {"n_nodes": 16},
         "traffic": {"segment_slots": 4, "horizon_slots": 8,
                     "sample_nodes": 8}}
SEED = 2 ** 33 + 1234

FOUR_DEVICES = f"""
import json, sys, time
sys.path[:0] = [{str(common.ROOT)!r}, {str(common.ROOT / "src")!r}]
import jax
from bench import common, faults
from bench.drivers import fleet
cfg = common.load_json(common.BENCH / "configs" / "har_wearables_x4.json")
traffic = common.load_json(common.BENCH / "traffic" / "fleet.json")
limits = common.load_json(common.BENCH / "limits"
                          / "har_wearables_x4.fleet.json")
cfg.update({SIZES['config']!r})
traffic.update({SIZES['traffic']!r})
out = {{}}
for fault in (None, "exchange_left_out"):
    with faults.planted(fault):
        rec = fleet.run({{}}, cfg, traffic, limits, {SEED}, 0.3, False,
                        jax.devices()[:4], time.perf_counter())
    out[str(fault)] = {{k: rec[k] for k in ("correct", "attempted",
                                            "failed")}}
    out[str(fault)]["devices"] = len(rec["devices"])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", FOUR_DEVICES], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_sound_run_is_correct(four_device_runs):
    out = four_device_runs["None"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 1
    assert out["devices"] == 4


def test_four_chip_exchange_left_out_is_caught(four_device_runs):
    out = four_device_runs["exchange_left_out"]
    assert not out["correct"] and out["failed"] == out["attempted"]
