"""Faults planted underneath the host cell's timed path, to show that the
comparison catches them.  Each wraps the program's ``host_serve_slot`` as
the host driver calls it (or, for ``edf_altered``, the pop inside it):

* ``state_unchanged`` — every slot hands back the state it was given, so
  the host never advances past its boot state;
* ``half_batch`` — half of every ingest lane is left out: the second half
  of the rows is masked off;
* ``answer_altered`` — the first served row of every slot answers with +1
  on its first logit;
* ``edf_altered`` — the pop takes entries by arrival instead of deadline
  (first come, first served: earliest arrival, then the order the lane
  carried them), after the same expiry.  With one deadline a slot, this
  differs from EDF's ties by queue slot once the ring has wrapped.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "answer_altered", "edf_altered")


def _fifo_pop(q, batch_size: int, now=None):
    """``edf_pop_batch`` with the order by arrival, then lane order."""
    import jax
    import jax.numpy as jnp

    from repro.host import MicroBatch, NO_DEADLINE, expire_deadlines
    missed = jnp.zeros((), jnp.int32)
    if now is not None:
        q, missed = expire_deadlines(q, now)
    by_node = jnp.argsort(jnp.where(q.valid, q.node_id, NO_DEADLINE),
                          stable=True)
    keys = jnp.where(q.valid, q.arrival, NO_DEADLINE)[by_node]
    take = by_node[jnp.argsort(keys, stable=True)][:batch_size]
    batch = MicroBatch(
        payload=jax.tree_util.tree_map(lambda a: a[take], q.payload),
        node_id=q.node_id[take], arrival=q.arrival[take],
        deadline=q.deadline[take], valid=q.valid[take])
    return q._replace(valid=q.valid.at[take].set(False)), batch, missed


def _wrap(fault: str, orig):
    def fn(state, entries, node_ids, mask, **kw):
        import jax
        import jax.numpy as jnp
        if fault == "state_unchanged":
            # a copy, since the slot may donate the state it is given
            given = jax.tree_util.tree_map(jnp.copy, state)
            _, out = orig(state, entries, node_ids, mask, **kw)
            return given, out
        if fault == "half_batch":
            h = mask.shape[0] // 2
            return orig(state, entries, node_ids, mask.at[h:].set(False),
                        **kw)
        if fault == "answer_altered":
            new, out = orig(state, entries, node_ids, mask, **kw)
            first = jnp.argmax(out.valid)
            return new, out._replace(logits=out.logits.at[first, 0].add(
                jnp.where(out.valid[first], 1.0, 0.0)))
        raise ValueError(f"unknown fault {fault!r}; options: {FAULTS}")
    return fn


@contextlib.contextmanager
def planted(fault: str | None):
    """Plant ``fault`` (None plants nothing) for the duration of the
    block."""
    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; options: {FAULTS}")
    import repro.host as host
    from repro.host import server
    if fault == "edf_altered":
        orig = server.edf_pop_batch
        server.edf_pop_batch = _fifo_pop
        server._build_serve_slot.cache_clear()
        try:
            yield
        finally:
            server.edf_pop_batch = orig
            server._build_serve_slot.cache_clear()
        return
    orig = host.host_serve_slot
    host.host_serve_slot = _wrap(fault, orig)
    try:
        yield
    finally:
        host.host_serve_slot = orig
