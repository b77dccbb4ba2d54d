"""Faults planted underneath the timed path, to show that the comparison
catches them.  Each wraps the program's fleet engine
(``seeker_fleet_simulate`` and ``seeker_fleet_simulate_sharded``) as the
fleet driver calls it:

* ``state_unchanged`` — every segment hands back the state it was given,
  so the deployment never advances past its first segment;
* ``half_batch`` — half of the fleet's inputs are left out: the second
  half of the nodes is fed the first half's windows and harvest;
* ``answer_altered`` — the host answer of every node in a segment's first
  slot is altered where it is produced (+1 on the first class's logit);
* ``label_altered`` — every on-node answer (the D0 and D2 label, which
  rides the logits as a one-hot) names the next class instead;
* ``exchange_left_out`` — the fleet-wide aggregates (decision histogram,
  alive slots, exact wire bytes) are what the first shard's nodes alone
  give, as if the psum across chips were left out.  It needs a mesh of
  more than one device.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "answer_altered",
          "label_altered", "exchange_left_out")
ENGINES = ("seeker_fleet_simulate", "seeker_fleet_simulate_sharded")


def _first_shard_aggregates(res: dict, n_first: int) -> dict:
    import jax.numpy as jnp
    alive = res["alive"][:, :n_first]
    dec = jnp.where(alive, res["decisions"][:, :n_first], -1)
    n_dec = res["decision_histogram"].shape[0]
    hist = jnp.sum(dec[..., None] == jnp.arange(n_dec), axis=(0, 1))
    pay = jnp.where(alive, jnp.round(res["payload_bytes"][:, :n_first]), 0)
    total = jnp.sum(pay.astype(jnp.int32))
    return {"decision_histogram": hist.astype(jnp.int32),
            "alive_slots": jnp.sum(alive.astype(jnp.int32)),
            "bytes_on_wire_i32": jnp.stack([total // 2 ** 16,
                                            total % 2 ** 16])}


def _wrap(fault: str, orig):
    def fn(windows, harvest, **kw):
        if fault == "state_unchanged":
            import jax
            import jax.numpy as jnp
            # copies, since the engine donates the state it is given
            given = jax.tree_util.tree_map(jnp.copy, (
                kw["state0"], kw["node_keys"], kw["brownout_state0"]))
            res = dict(orig(windows, harvest, **kw))
            (res["final_state"], res["final_keys"],
             res["final_brownout"]) = given
            return res
        if fault == "half_batch":
            h = windows.shape[0] // 2
            windows = windows.at[h:2 * h].set(windows[:h])
            harvest = harvest.at[h:2 * h].set(harvest[:h])
            return orig(windows, harvest, **kw)
        if fault == "answer_altered":
            res = dict(orig(windows, harvest, **kw))
            res["logits"] = res["logits"].at[0, :, 0].add(1.0)
            return res
        if fault == "label_altered":
            import jax.numpy as jnp
            res = dict(orig(windows, harvest, **kw))
            on_node = (res["decisions"] == 0) | (res["decisions"] == 2)
            res["logits"] = jnp.where(on_node[..., None],
                                      jnp.roll(res["logits"], 1, axis=-1),
                                      res["logits"])
            return res
        if fault == "exchange_left_out":
            mesh = kw.get("mesh")
            if mesh is None or mesh.size < 2:
                raise ValueError("exchange_left_out needs a mesh of more "
                                 "than one device")
            res = dict(orig(windows, harvest, **kw))
            res.update(_first_shard_aggregates(
                res, windows.shape[0] // mesh.size))
            return res
        raise ValueError(f"unknown fault {fault!r}; options: {FAULTS}")

    return fn


@contextlib.contextmanager
def planted(fault: str | None):
    """Plant ``fault`` (None plants nothing) for the duration of the block."""
    if fault is None:
        yield
        return
    import repro.serving as serving
    origs = {name: getattr(serving, name) for name in ENGINES}
    for name, orig in origs.items():
        setattr(serving, name, _wrap(fault, orig))
    try:
        yield
    finally:
        for name, orig in origs.items():
            setattr(serving, name, orig)
