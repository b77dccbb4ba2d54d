"""Sharded fleet engine tests.

The equivalence contract: on a CPU mesh (subprocess with
``--xla_force_host_platform_device_count=8``, same pattern as
test_sharding_and_dryrun.py), :func:`seeker_fleet_simulate_sharded` must
reproduce :func:`seeker_fleet_simulate` BITWISE — decisions, payload bytes,
stored µJ, k trace, and (with a common ``node_block`` pinning XLA's
batch-shape-dependent matmul lowering) host logits — for both a divisible
N=8 and a non-divisible N=13, the latter exercising the pad-to-quantum /
inert-node masking path.  Fleet aggregates (bytes on wire, decision
histogram, completion, accuracy) are the only psum-ed quantities and are
checked against recomputation from the unsharded traces.

The state0-resume fix (two chained runs == one long run) needs no mesh and
runs in-process.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.seeker_har import HAR
from repro.core import fleet_harvest_traces
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.models.har import har_init
from repro.serving import seeker_fleet_simulate

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_EQUIV_CODE = """
import re
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.seeker_har import HAR
from repro.core import fleet_harvest_traces
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.models.har import har_init
from repro.serving import seeker_fleet_simulate, seeker_fleet_simulate_sharded
from repro.sharding import make_mesh_compat

assert jax.device_count() == 8, jax.device_count()
S, BLOCK = 6, 4
key = jax.random.PRNGKey(0)
params = har_init(key, HAR)
gen = init_generator(key, HAR.window, HAR.channels)
sigs = class_signatures()
wins, labels = har_stream(key, S)

for n, mesh in ((8, make_mesh_compat((8,), ("data",))),
                (13, make_mesh_compat((8,), ("data",))),
                (13, make_mesh_compat((2, 4), ("pod", "data")))):
    harvest = fleet_harvest_traces(key, n, S)
    ref = seeker_fleet_simulate(
        wins, harvest, signatures=sigs, qdnn_params=params,
        host_params=params, gen_params=gen, har_cfg=HAR,
        node_block=BLOCK, donate=False)
    sh = seeker_fleet_simulate_sharded(
        wins, harvest, signatures=sigs, qdnn_params=params,
        host_params=params, gen_params=gen, har_cfg=HAR, mesh=mesh,
        labels=labels, node_block=BLOCK, donate=False)
    assert sh["padded_nodes"] == (-n) % 8, sh["padded_nodes"]

    # --- bitwise per-node traces (the acceptance contract) -----------------
    for k in ("decisions", "payload_bytes", "stored_uj", "k_trace",
              "logits", "preds"):
        np.testing.assert_array_equal(
            np.asarray(sh[k]), np.asarray(ref[k]),
            err_msg=f"{k} (N={n}, mesh {mesh.shape})")
    np.testing.assert_array_equal(
        np.asarray(sh["final_state"].stored_uj),
        np.asarray(ref["final_state"].stored_uj))

    # --- psum-ed fleet aggregates vs recomputation from unsharded traces ---
    dec = np.asarray(ref["decisions"])
    sent = dec != 5
    np.testing.assert_allclose(float(sh["bytes_on_wire"]),
                               float(ref["bytes_on_wire"]),
                               rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(sh["decision_histogram"]),
        np.bincount(dec.ravel(), minlength=6))
    assert abs(float(sh["completed_frac"]) - sent.mean()) < 1e-6
    correct = ((np.asarray(ref["preds"]) == np.asarray(labels)[:, None])
               & sent).sum()
    want = correct / max(sent.sum(), 1)
    assert abs(float(sh["fleet_accuracy"]) - want) < 1e-6
    print(f"N={n} mesh={mesh.shape} OK")

# default path (node_block=None, full-batch vmap): integer and energy traces
# stay bitwise; logits only to tolerance (XLA batch-shape matmul lowering)
n, mesh = 13, make_mesh_compat((8,), ("data",))
harvest = fleet_harvest_traces(key, n, S)
ref = seeker_fleet_simulate(
    wins, harvest, signatures=sigs, qdnn_params=params, host_params=params,
    gen_params=gen, har_cfg=HAR, donate=False)
sh = seeker_fleet_simulate_sharded(
    wins, harvest, signatures=sigs, qdnn_params=params, host_params=params,
    gen_params=gen, har_cfg=HAR, mesh=mesh, donate=False)
for k in ("decisions", "payload_bytes", "stored_uj", "k_trace"):
    np.testing.assert_array_equal(np.asarray(sh[k]), np.asarray(ref[k]),
                                  err_msg=f"{k} (default node_block)")
np.testing.assert_allclose(np.asarray(sh["logits"]), np.asarray(ref["logits"]),
                           rtol=1e-5, atol=1e-5)
print("default node_block OK")

# the psums of the fleet aggregates carry the collectives scope into the
# compiled program, where a device trace reads it
hlo = jax.jit(lambda w, h: seeker_fleet_simulate_sharded(
    w, h, signatures=sigs, qdnn_params=params, host_params=params,
    gen_params=gen, har_cfg=HAR, mesh=mesh, donate=False)[
        "decision_histogram"]).lower(wins, harvest).compile().as_text()
assert re.search(r"all-reduce.*seeker[.]collectives", hlo), "no scoped psum"
print("collectives scope OK")
print("OK")
"""


_SERVE_CODE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.seeker_har import HAR
from repro.core.coreset import ClusterCoreset, channel_cluster_coresets
from repro.core.recovery import recover_cluster_window
from repro.data.sensors import har_stream
from repro.models.har import har_apply, har_init
from repro.serving import fleet_serve_step
from repro.serving.edge_host import (decode_wire_coresets,
                                     encode_wire_coresets)
from repro.sharding import make_mesh_compat

key = jax.random.PRNGKey(0)
params = har_init(key, HAR)
for n, shape, axes in ((16, (8,), ("data",)), (13, (8,), ("data",)),
                       (16, (2, 4), ("pod", "data"))):
    wins, _ = har_stream(jax.random.PRNGKey(2), n)
    mesh = make_mesh_compat(shape, axes)
    out = fleet_serve_step(wins, host_params=params, har_cfg=HAR, mesh=mesh,
                           key=key)
    assert out["host_logits"].shape == (n, HAR.n_classes)
    assert out["wire_bytes"] < out["raw_bytes"]
    # unsharded host-side oracle on the padded fleet (same key split count)
    pad = (-n) % 8
    wp = jnp.pad(wins, ((0, pad), (0, 0), (0, 0)))
    c, r, cnt = jax.vmap(
        lambda w: channel_cluster_coresets(w, k=12, iters=4))(wp)
    cr, rr, nr = decode_wire_coresets(encode_wire_coresets(c, r, cnt))
    keys = jax.random.split(key, n + pad)
    rec = jax.vmap(lambda cc, rad, cn, kk: recover_cluster_window(
        ClusterCoreset(cc, rad, cn), kk, HAR.window))(cr, rr, nr, keys)
    np.testing.assert_array_equal(np.asarray(out["host_logits"]),
                                  np.asarray(har_apply(params, rec)[:n]),
                                  err_msg=f"n={n} mesh={shape}")
    print(f"n={n} mesh={shape} OK")
print("OK")
"""


_CHURN_CODE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.seeker_har import HAR
from repro.core import fleet_alive_traces, fleet_harvest_traces
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.models.har import har_init
from repro.serving import (seeker_fleet_simulate,
                           seeker_fleet_simulate_sharded,
                           seeker_fleet_simulate_streamed)
from repro.sharding import make_mesh_compat

assert jax.device_count() == 8, jax.device_count()
S, N, BLOCK = 6, 13, 4
key = jax.random.PRNGKey(0)
params = har_init(key, HAR)
gen = init_generator(key, HAR.window, HAR.channels)
sigs = class_signatures()
wins, labels = har_stream(key, S)
harvest = fleet_harvest_traces(key, N, S)
alive = fleet_alive_traces(key, N, S, duty=0.6, period=4)
assert bool(jnp.any(~alive)), "fixture must churn"
mesh = make_mesh_compat((8,), ("data",))
kw = dict(signatures=sigs, qdnn_params=params, host_params=params,
          gen_params=gen, har_cfg=HAR, node_block=BLOCK, donate=False)

# --- churn: sharded == single-device bitwise under the same alive trace ---
ref = seeker_fleet_simulate(wins, harvest, alive=alive, labels=labels, **kw)
sh = seeker_fleet_simulate_sharded(wins, harvest, alive=alive, labels=labels,
                                   mesh=mesh, **kw)
for k in ("decisions", "payload_bytes", "stored_uj", "k_trace", "logits",
          "preds"):
    np.testing.assert_array_equal(np.asarray(sh[k]), np.asarray(ref[k]),
                                  err_msg=k)
np.testing.assert_array_equal(np.asarray(sh["final_keys"]),
                              np.asarray(ref["final_keys"]))
# psum'd aggregates == the single-device engine's (ints exactly)
for k in ("decision_histogram", "completed", "alive_slots", "correct"):
    np.testing.assert_array_equal(np.asarray(sh[k]), np.asarray(ref[k]),
                                  err_msg=k)
np.testing.assert_allclose(float(sh["bytes_on_wire"]),
                           float(ref["bytes_on_wire"]), rtol=1e-6)
assert abs(float(sh["completed_frac"]) - float(ref["completed_frac"])) < 1e-6
assert abs(float(sh["fleet_accuracy"]) - float(ref["fleet_accuracy"])) < 1e-6
# the histogram ignores dead slots: recompute from the alive mask
a = np.asarray(alive).T
np.testing.assert_array_equal(
    np.asarray(sh["decision_histogram"]),
    np.bincount(np.asarray(ref["decisions"])[a].ravel(), minlength=6))
print("churn equivalence OK")

# --- per-node (S, N) labels: the headline accuracy bugfix, sharded --------
wn = jnp.stack([wins + 0.01 * i for i in range(N)])       # per-node streams
tracks = jnp.stack([jnp.roll(labels, i) for i in range(N)], axis=1)
refp = seeker_fleet_simulate(wn, harvest, labels=tracks, **kw)
shp = seeker_fleet_simulate_sharded(wn, harvest, labels=tracks, mesh=mesh,
                                    **kw)
np.testing.assert_array_equal(np.asarray(shp["correct"]),
                              np.asarray(refp["correct"]))
sent = np.asarray(refp["decisions"]) != 5
want = ((np.asarray(refp["preds"]) == np.asarray(tracks)) & sent).sum()
assert int(shp["correct"]) == want, (int(shp["correct"]), want)
assert abs(float(shp["fleet_accuracy"])
           - want / max(sent.sum(), 1)) < 1e-6
# shared (S,) track with per-node streams refuses (the old silent bug)
try:
    seeker_fleet_simulate_sharded(wn, harvest, labels=labels, mesh=mesh,
                                  **kw)
    raise SystemExit("shared labels with per-node streams must raise")
except ValueError as e:
    assert "ambiguous" in str(e)
print("per-node labels OK")

# --- streamed sharded == one long sharded run bitwise ---------------------
stream = seeker_fleet_simulate_streamed(
    wins, harvest, chunk=4, alive=alive, labels=labels, mesh=mesh, **kw)
for k in ("decisions", "payload_bytes", "stored_uj", "logits"):
    np.testing.assert_array_equal(np.asarray(stream[k]), np.asarray(sh[k]),
                                  err_msg="streamed " + k)
np.testing.assert_array_equal(np.asarray(stream["final_keys"]),
                              np.asarray(sh["final_keys"]))
for k in ("decision_histogram", "completed", "alive_slots", "correct"):
    np.testing.assert_array_equal(np.asarray(stream[k]), np.asarray(sh[k]),
                                  err_msg="streamed " + k)
assert stream["n_chunks"] == 2 and stream["padded_nodes"] == 3
print("streamed sharded OK")
print("OK")
"""


_BROWNOUT_CODE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.seeker_har import HAR
from repro.core import BrownoutConfig, fleet_alive_traces, \\
    fleet_harvest_traces
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.models.har import har_init
from repro.serving import (seeker_fleet_simulate,
                           seeker_fleet_simulate_sharded,
                           seeker_fleet_simulate_streamed, wire_bytes_exact)
from repro.sharding import make_mesh_compat

assert jax.device_count() == 8, jax.device_count()
S, N, BLOCK = 8, 13, 4
key = jax.random.PRNGKey(0)
params = har_init(key, HAR)
gen = init_generator(key, HAR.window, HAR.channels)
sigs = class_signatures()
wins, labels = har_stream(key, S)
harvest = fleet_harvest_traces(key, N, S)
exo = fleet_alive_traces(key, N, S, duty=0.8, period=4)
cfg = BrownoutConfig(off_uj=8.0, restart_uj=28.0)
mesh = make_mesh_compat((8,), ("data",))
kw = dict(signatures=sigs, qdnn_params=params, host_params=params,
          gen_params=gen, har_cfg=HAR, node_block=BLOCK, donate=False,
          brownout=cfg, initial_uj=10.0, labels=labels, alive=exo)

# --- endogenous brown-out: sharded == single-device bitwise, N=13 pads ----
ref = seeker_fleet_simulate(wins, harvest, **kw)
assert bool(jnp.any(ref["brownout"])), "fixture must brown out"
sh = seeker_fleet_simulate_sharded(wins, harvest, mesh=mesh, **kw)
assert sh["padded_nodes"] == 3
for k in ("decisions", "payload_bytes", "stored_uj", "k_trace", "logits",
          "alive", "brownout"):
    np.testing.assert_array_equal(np.asarray(sh[k]), np.asarray(ref[k]),
                                  err_msg=k)
np.testing.assert_array_equal(np.asarray(sh["final_brownout"]),
                              np.asarray(ref["final_brownout"]))
np.testing.assert_array_equal(np.asarray(sh["final_keys"]),
                              np.asarray(ref["final_keys"]))
# psum'd realism counters == single-device ints EXACTLY (acceptance), and
# the padding nodes never browned in (their slots are outside every count)
for k in ("brownout_slots", "brownout_events", "completed", "alive_slots",
          "correct"):
    assert int(sh[k]) == int(ref[k]), (k, int(sh[k]), int(ref[k]))
a = np.asarray(ref["alive"]); b = np.asarray(ref["brownout"])
e = np.asarray(exo).T
np.testing.assert_array_equal(a, e & ~b)          # composition rule
assert int(sh["alive_slots"]) + int(sh["brownout_slots"]) == e.sum()
# exact int byte pair: psum'd == single-device == int64 recomputation
want = int(np.asarray(ref["payload_bytes"], np.int64)[a].sum())
assert wire_bytes_exact(sh) == wire_bytes_exact(ref) == want
print("sharded brown-out OK")

# --- streamed sharded: the flag rides the resume contract ------------------
stream = seeker_fleet_simulate_streamed(wins, harvest, chunk=3, mesh=mesh,
                                        **kw)
for k in ("decisions", "stored_uj", "logits", "alive", "brownout"):
    np.testing.assert_array_equal(np.asarray(stream[k]), np.asarray(sh[k]),
                                  err_msg="streamed " + k)
for k in ("brownout_slots", "brownout_events", "completed", "alive_slots"):
    assert int(stream[k]) == int(sh[k]), k
np.testing.assert_array_equal(np.asarray(stream["final_brownout"]),
                              np.asarray(sh["final_brownout"]))
assert wire_bytes_exact(stream) == wire_bytes_exact(sh)
print("streamed sharded brown-out OK")
print("OK")
"""


_INTERMITTENT_CODE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.seeker_har import HAR
from repro.core import (BrownoutConfig, IntermittentConfig,
                        fleet_harvest_traces)
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.models.har import har_aux_init, har_init
from repro.serving import (seeker_fleet_simulate,
                           seeker_fleet_simulate_sharded,
                           seeker_fleet_simulate_streamed, wire_bytes_exact)
from repro.sharding import make_mesh_compat

assert jax.device_count() == 8, jax.device_count()
S, N, BLOCK = 8, 13, 4
key = jax.random.PRNGKey(0)
params = har_init(key, HAR)
aux = har_aux_init(jax.random.fold_in(key, 7), HAR)
gen = init_generator(key, HAR.window, HAR.channels)
wins, labels = har_stream(key, S)
harvest = fleet_harvest_traces(key, N, S) * 0.04      # scarce: DEFER-heavy
mesh = make_mesh_compat((8,), ("data",))
kw = dict(signatures=class_signatures(), qdnn_params=params,
          host_params=params, gen_params=gen, har_cfg=HAR, node_block=BLOCK,
          donate=False, initial_uj=12.0, labels=labels,
          brownout=BrownoutConfig(), intermittent=IntermittentConfig(),
          aux_params=aux)

IT_KEYS = ("decisions", "payload_bytes", "stored_uj", "logits", "alive",
           "brownout", "it_emit", "it_label", "it_conf", "it_src",
           "it_stage")
COUNTERS = ("completed", "alive_slots", "brownout_slots", "it_full",
            "it_early", "correct", "correct_ladder", "it_correct_full",
            "it_correct_early")

# --- intermittent lane: sharded == single-device bitwise, N=13 pads --------
ref = seeker_fleet_simulate(wins, harvest, **kw)
assert int(ref["it_full"]) + int(ref["it_early"]) > 0, "lane must emit"
sh = seeker_fleet_simulate_sharded(wins, harvest, mesh=mesh, **kw)
assert sh["padded_nodes"] == 3
for k in IT_KEYS:
    np.testing.assert_array_equal(np.asarray(sh[k]), np.asarray(ref[k]),
                                  err_msg=k)
for k in COUNTERS:
    assert int(sh[k]) == int(ref[k]), (k, int(sh[k]), int(ref[k]))
np.testing.assert_array_equal(np.asarray(sh["decision_histogram"]),
                              np.asarray(ref["decision_histogram"]))
jax.tree_util.tree_map(
    lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
    sh["final_intermittent"], ref["final_intermittent"])
assert wire_bytes_exact(sh) == wire_bytes_exact(ref)
print("sharded intermittent OK")

# --- streamed sharded: suspended progress rides the resume contract --------
stream = seeker_fleet_simulate_streamed(wins, harvest, chunk=3, mesh=mesh,
                                        **kw)
for k in IT_KEYS:
    np.testing.assert_array_equal(np.asarray(stream[k]), np.asarray(sh[k]),
                                  err_msg="streamed " + k)
for k in COUNTERS:
    assert int(stream[k]) == int(sh[k]), k
jax.tree_util.tree_map(
    lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
    stream["final_intermittent"], sh["final_intermittent"])
print("streamed sharded intermittent OK")
print("OK")
"""


_TELEMETRY_CODE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.seeker_har import HAR
from repro.core import BrownoutConfig, fleet_harvest_traces
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.models.har import har_init
from repro.obs import counter_value
from repro.serving import (seeker_fleet_simulate,
                           seeker_fleet_simulate_sharded,
                           seeker_fleet_simulate_streamed, wire_bytes_exact)
from repro.sharding import make_mesh_compat

assert jax.device_count() == 8, jax.device_count()
S, N, BLOCK = 6, 13, 4
key = jax.random.PRNGKey(0)
params = har_init(key, HAR)
gen = init_generator(key, HAR.window, HAR.channels)
wins, labels = har_stream(key, S)
harvest = fleet_harvest_traces(key, N, S)
mesh = make_mesh_compat((8,), ("data",))
kw = dict(signatures=class_signatures(), qdnn_params=params,
          host_params=params, gen_params=gen, har_cfg=HAR, labels=labels,
          node_block=BLOCK, donate=False,
          brownout=BrownoutConfig(off_uj=8.0, restart_uj=28.0),
          initial_uj=10.0)

# --- registry lanes: single-device == sharded == streamed, bitwise --------
ref = seeker_fleet_simulate(wins, harvest, telemetry=True, **kw)
sh = seeker_fleet_simulate_sharded(wins, harvest, mesh=mesh, telemetry=True,
                                   **kw)
stream = seeker_fleet_simulate_streamed(wins, harvest, chunk=4, mesh=mesh,
                                        telemetry=True, **kw)
spec = ref["telemetry_spec"]
assert sh["telemetry_spec"] is spec and stream["telemetry_spec"] is spec
for name in spec.names():
    np.testing.assert_array_equal(np.asarray(sh["telemetry"][name]),
                                  np.asarray(ref["telemetry"][name]),
                                  err_msg="sharded " + name)
    np.testing.assert_array_equal(np.asarray(stream["telemetry"][name]),
                                  np.asarray(ref["telemetry"][name]),
                                  err_msg="streamed " + name)
# counters are exact ints, equal to the engine's own psum'd aggregates
tel = sh["telemetry"]
assert counter_value(tel, "fleet.wire_bytes") == wire_bytes_exact(sh)
assert counter_value(tel, "fleet.completed") == int(sh["completed"])
assert counter_value(tel, "fleet.alive_slots") == int(sh["alive_slots"])
assert counter_value(tel, "fleet.brownout_slots") == int(sh["brownout_slots"])
assert counter_value(tel, "fleet.brownout_events") \\
    == int(sh["brownout_events"])
np.testing.assert_array_equal(np.asarray(tel["fleet.decisions"]),
                              np.asarray(sh["decision_histogram"]))
print("telemetry lanes OK")

# --- telemetry=None leaves the sharded engine bitwise untouched ------------
off = seeker_fleet_simulate_sharded(wins, harvest, mesh=mesh, **kw)
assert "telemetry" not in off
for k in ("decisions", "payload_bytes", "stored_uj", "logits", "alive",
          "brownout"):
    np.testing.assert_array_equal(np.asarray(off[k]), np.asarray(sh[k]),
                                  err_msg="off " + k)
print("telemetry=None OK")
print("OK")
"""


_PER_SHARD_HOST_CODE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.seeker_har import HAR
from repro.core.recovery import init_generator
from repro.data.sensors import har_stream
from repro.models.har import har_init
from repro.serving import fleet_serve_step
from repro.host import (HostServeConfig, host_server_init,
                        host_server_init_stacked, host_server_stats)
from repro.sharding import make_mesh_compat

assert jax.device_count() == 8
key = jax.random.PRNGKey(0)
params = har_init(key, HAR)
gen = init_generator(key, HAR.window, HAR.channels)
n = 13
wins, _ = har_stream(jax.random.PRNGKey(2), n)
cfg = HostServeConfig(channels=HAR.channels, k=12, m=20, t=HAR.window,
                      n_classes=HAR.n_classes, n_nodes=n, batch_size=4,
                      queue_capacity=16, cache_capacity=16, qos_slots=4)
alive = jnp.asarray([True] * 8 + [False] * 5)

def by_node(so):
    v = np.asarray(so.valid)
    return {int(nn): np.asarray(so.logits)[i]
            for i, nn in enumerate(np.asarray(so.node_id)) if v[i]}

for shape, axes in (((8,), ("data",)), ((2, 4), ("pod", "data"))):
    mesh = make_mesh_compat(shape, axes)
    central = fleet_serve_step(
        wins, host_params=params, har_cfg=HAR, mesh=mesh, key=key,
        host_state=host_server_init(cfg), serve_cfg=cfg, gen_params=gen,
        alive=alive)
    st = host_server_init_stacked(cfg, 8)
    ps = fleet_serve_step(
        wins, host_params=params, har_cfg=HAR, mesh=mesh, key=key,
        host_state=st, serve_cfg=cfg, gen_params=gen, alive=alive,
        per_shard_host=True)
    # psum'd QoS counters: every alive node served, nothing lost
    assert ps["qos"] == {"served": 8, "deadline_misses": 0,
                         "drops_overflow": 0}, ps["qos"]
    a, b = by_node(central["slot_output"]), by_node(ps["slot_output"])
    assert sorted(a) == sorted(b) == [0, 1, 2, 3, 4, 5, 6, 7]
    # payload-deterministic recovery PRNG: each node's answer matches the
    # central queue's (row-independent host DNN at the same batch shape)
    for nn in a:
        np.testing.assert_allclose(a[nn], b[nn], rtol=1e-6, atol=1e-6,
                                   err_msg=f"node {nn} mesh {shape}")
    # the stacked carry resumes: a second identical round is cache-served
    ps2 = fleet_serve_step(
        wins, host_params=params, har_cfg=HAR, mesh=mesh, key=key,
        host_state=ps["host_state"], serve_cfg=cfg, gen_params=gen,
        alive=alive, per_shard_host=True)
    assert ps2["qos"]["served"] == 16
    hits = int(jnp.sum(ps2["host_state"].cache.hits))
    assert hits == 8, hits
    b2 = by_node(ps2["slot_output"])
    for nn in b:
        np.testing.assert_array_equal(b[nn], b2[nn])
    print(f"mesh {shape} OK")
print("OK")
"""


_MIXED_TASK_CODE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.seeker_har import HAR
from repro.core import BrownoutConfig, fleet_harvest_traces
from repro.core.recovery import init_generator
from repro.data.sensors import class_signatures, har_stream
from repro.models.har import har_init
from repro.serving import (TaskLaneConfig, seeker_fleet_simulate,
                           seeker_fleet_simulate_sharded,
                           seeker_fleet_simulate_streamed)
from repro.sharding import make_mesh_compat

assert jax.device_count() == 8, jax.device_count()
S, N, BLOCK = 6, 13, 4
key = jax.random.PRNGKey(0)
params = har_init(key, HAR)
gen = init_generator(key, HAR.window, HAR.channels)
wins, labels = har_stream(key, S)
harvest = fleet_harvest_traces(key, N, S)
mesh = make_mesh_compat((8,), ("data",))
cfg = TaskLaneConfig()   # round-robin har/bearing ids, bearing cost scale
kw = dict(signatures=class_signatures(), qdnn_params=params,
          host_params=params, gen_params=gen, har_cfg=HAR, labels=labels,
          node_block=BLOCK, donate=False, task=cfg,
          brownout=BrownoutConfig(off_uj=8.0, restart_uj=28.0),
          initial_uj=10.0)

ref = seeker_fleet_simulate(wins, harvest, **kw)
sh = seeker_fleet_simulate_sharded(wins, harvest, mesh=mesh, **kw)
stream = seeker_fleet_simulate_streamed(wins, harvest, chunk=4, mesh=mesh,
                                        **kw)

# --- mixed fleet traces bitwise across all three drivers -------------------
for k in ("decisions", "payload_bytes", "stored_uj", "logits", "alive",
          "brownout"):
    np.testing.assert_array_equal(np.asarray(sh[k]), np.asarray(ref[k]),
                                  err_msg="sharded " + k)
    np.testing.assert_array_equal(np.asarray(stream[k]), np.asarray(ref[k]),
                                  err_msg="streamed " + k)
assert sh["task_names"] == stream["task_names"] == ("har", "bearing")
np.testing.assert_array_equal(np.asarray(sh["tasks"]), np.asarray(ref["tasks"]))
print("mixed traces OK")

# --- per-task splits: psum'd ints EXACTLY equal single-device --------------
for k in ("completed_by_task", "deadline_miss_by_task", "correct_by_task"):
    np.testing.assert_array_equal(np.asarray(sh[k]), np.asarray(ref[k]),
                                  err_msg="sharded " + k)
    np.testing.assert_array_equal(np.asarray(stream[k]), np.asarray(ref[k]),
                                  err_msg="streamed " + k)

# recompute the split from the unsharded traces: padding (N=13 on 8 devices)
# must never enter a per-task count
tasks = np.asarray(ref["tasks"])
sent = (np.asarray(ref["decisions"]) != 5) & np.asarray(ref["alive"])
comp = np.asarray(sh["completed_by_task"])
miss = np.asarray(sh["deadline_miss_by_task"])
for t in range(cfg.n_tasks):
    assert comp[t] == sent[:, tasks == t].sum(), t
assert comp.sum() == int(sh["completed"])
assert comp.sum() + miss.sum() == int(sh["alive_slots"])
ok = np.asarray(ref["preds"]) == np.asarray(labels)[:, None]
corr = np.asarray(sh["correct_by_task"])
for t in range(cfg.n_tasks):
    assert corr[t] == (ok & sent)[:, tasks == t].sum(), t
print("per-task psum splits OK")
print("OK")
"""


@pytest.mark.slow
def test_sharded_mixed_task_fleet_psum_exact_8dev():
    """ISSUE 9 acceptance on the mesh: a mixed HAR+bearing fleet (task lane,
    round-robin ids, bearing cost scale) is bitwise identical single-device
    vs sharded vs streamed under brown-outs with N=13 padding, and the
    per-task aggregate splits (completed / deadline-miss / correct by task)
    are psum-exact integers that partition the fleet totals, recomputed
    from the unsharded traces."""
    assert "OK" in _run(_MIXED_TASK_CODE, devices=8)


@pytest.mark.slow
def test_sharded_fleet_bitwise_equivalence_8dev():
    """Sharded == unsharded bitwise on an 8-virtual-device CPU mesh, for
    divisible N=8, non-divisible N=13 (padding/masking path), and a 2-axis
    ("pod","data") mesh."""
    assert "OK" in _run(_EQUIV_CODE, devices=8)


@pytest.mark.slow
def test_sharded_churn_labels_streaming_8dev():
    """ISSUE 4 acceptance on the sharded engine: churn bitwise-equivalence
    against the single-device engine under one alive trace, per-node (S, N)
    label accuracy (psum'd ints exactly equal), the shared-track refusal,
    and streamed == one long sharded run."""
    assert "OK" in _run(_CHURN_CODE, devices=8)


@pytest.mark.slow
def test_sharded_brownout_parity_8dev():
    """ISSUE 5 acceptance on the mesh: endogenous brown-out churn is bitwise
    identical single-device vs sharded vs streamed — alive/brownout lanes,
    the psum'd ``brownout_slots``/``brownout_events`` pair (exact ints), the
    exogenous∧endogenous composition rule, the padding-never-browns-in
    guarantee (N=13 on 8 devices), and the exact int32-pair byte counter
    against an int64 recomputation."""
    assert "OK" in _run(_BROWNOUT_CODE, devices=8)


@pytest.mark.slow
def test_sharded_intermittent_parity_8dev():
    """ISSUE 7 acceptance on the mesh: the staged intermittent-inference
    lane is bitwise identical single-device vs sharded vs streamed under
    scarce harvest + brown-outs — it_* traces, the 9-bin histogram, the
    psum'd completion/accuracy counters (exact ints), suspended progress
    chained through the resume contract, and padding nodes (N=13 on 8
    devices) never entering any lane aggregate."""
    assert "OK" in _run(_INTERMITTENT_CODE, devices=8)


@pytest.mark.slow
def test_sharded_telemetry_lane_parity_8dev():
    """ISSUE 8 acceptance on the mesh: every registry lane (exact int-pair
    counters, gauges, histograms) is bitwise identical single-device vs
    sharded (psum inside shard_map) vs streamed (metrics_merge across
    segments) under brown-out churn with N=13 padding, counters equal the
    engine's own aggregates, and ``telemetry=None`` leaves the sharded
    engine bitwise untouched."""
    assert "OK" in _run(_TELEMETRY_CODE, devices=8)


@pytest.mark.slow
def test_fleet_serve_step_per_shard_host_8dev():
    """Per-shard host serving (the ROADMAP multi-host shape on one
    process): each shard's own queue/EDF/cache serves its local tile, only
    QoS counters cross shards (psum), answers match the central queue mode,
    and the stacked carry resumes with cache hits."""
    assert "OK" in _run(_PER_SHARD_HOST_CODE, devices=8)


@pytest.mark.slow
def test_fleet_serve_step_gathers_payloads_8dev():
    """The edge->host tier gathers only wire-format coreset payloads across
    the mesh; host logits match the unsharded encode/decode/recover oracle
    bitwise (the host side runs at the full gathered batch either way)."""
    assert "OK" in _run(_SERVE_CODE, devices=8)


# ---------------------------------------------------------------------------
# state0 resume (the silently-reset-initial_uj fix) — no mesh needed
# ---------------------------------------------------------------------------

S = 12


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    params = har_init(key, HAR)
    gen = init_generator(key, HAR.window, HAR.channels)
    sigs = class_signatures()
    wins, labels = har_stream(key, S)
    return key, params, gen, sigs, wins, labels


def test_fleet_state0_is_used_not_reset(setup):
    """The fix: a passed ``state0`` must drive the run — the engine used to
    silently rebuild node state with the default ``initial_uj``."""
    key, params, gen, sigs, wins, labels = setup
    from repro.serving.fleet import fleet_node_init
    n = 5
    harvest = fleet_harvest_traces(key, n, S)
    kw = dict(signatures=sigs, qdnn_params=params, host_params=params,
              gen_params=gen, har_cfg=HAR, key=key, donate=False)

    low = seeker_fleet_simulate(wins, harvest,
                                state0=fleet_node_init(n, initial_uj=5.0),
                                **kw)
    # state0 at charge X == fresh init with initial_uj=X, bit for bit
    oracle = seeker_fleet_simulate(wins, harvest, initial_uj=5.0, **kw)
    np.testing.assert_array_equal(np.asarray(low["decisions"]),
                                  np.asarray(oracle["decisions"]))
    np.testing.assert_array_equal(np.asarray(low["stored_uj"]),
                                  np.asarray(oracle["stored_uj"]))
    # ... and differs from the default-init run the old code always did
    default = seeker_fleet_simulate(wins, harvest, **kw)
    assert not np.array_equal(np.asarray(low["stored_uj"]),
                              np.asarray(default["stored_uj"]))


def test_fleet_resume_chain_matches_one_long_run(setup):
    """Serving-loop resume: chaining ``final_state -> state0`` AND
    ``final_keys -> node_keys`` makes two runs bitwise equal to one long
    run — charge, predictor history, AAC continuity and every node's PRNG
    stream all continue where the previous segment stopped."""
    key, params, gen, sigs, wins, labels = setup
    n = 4
    harvest = fleet_harvest_traces(key, n, S)
    kw = dict(signatures=sigs, qdnn_params=params, host_params=params,
              gen_params=gen, har_cfg=HAR, key=key, donate=False)
    half = S // 2
    full = seeker_fleet_simulate(wins, harvest, **kw)
    first = seeker_fleet_simulate(wins[:half], harvest[:, :half], **kw)
    second = seeker_fleet_simulate(wins[half:], harvest[:, half:],
                                   state0=first["final_state"],
                                   node_keys=first["final_keys"], **kw)
    for k in ("decisions", "payload_bytes", "stored_uj", "logits"):
        np.testing.assert_array_equal(np.asarray(second[k]),
                                      np.asarray(full[k][half:]), err_msg=k)
    np.testing.assert_array_equal(np.asarray(second["final_state"].stored_uj),
                                  np.asarray(full["final_state"].stored_uj))
    np.testing.assert_array_equal(np.asarray(second["final_keys"]),
                                  np.asarray(full["final_keys"]))
    # and it is NOT the trajectory a silently-reset fleet would follow
    fresh = seeker_fleet_simulate(wins[half:], harvest[:, half:], **kw)
    assert not np.array_equal(np.asarray(second["stored_uj"]),
                              np.asarray(fresh["stored_uj"]))


def test_fleet_state0_wrong_size_raises(setup):
    key, params, gen, sigs, wins, labels = setup
    from repro.serving.fleet import fleet_node_init
    harvest = fleet_harvest_traces(key, 4, S)
    with pytest.raises(ValueError, match="stacked for"):
        seeker_fleet_simulate(wins, harvest, signatures=sigs,
                              qdnn_params=params, host_params=params,
                              gen_params=gen, har_cfg=HAR,
                              state0=fleet_node_init(3), donate=False)
