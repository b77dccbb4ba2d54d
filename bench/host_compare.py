"""The comparison that decides ``correct`` for the host cells.

Inputs are the program's and the reference's (``bench/host_reference.py``)
record of the same host slots, as lists of per-slot dicts: the served rows
(``node_id``, ``deadline``, ``valid``, ``cache_hit``, ``logits``), the
``counters`` after the slot (served, deadline misses, overflow drops,
backlog, cache hits, cache misses) and the cumulative per-node ``votes``.

Numbers (a cell compares those its ``bench/limits/<cell>.json`` lists,
each against its own limit):

* ``row_mismatch`` — rows whose ``valid`` flag, node, deadline or memo hit
  differs (EDF order, ties, expiry, padding and memo are integer
  decisions, so the limit is 0);
* ``logit_gap`` — on valid rows, the widest gap between the program's and
  the reference's logits, relative to the reference's largest logit
  magnitude (floor 1), as in the fleet cells;
* ``counter_mismatch`` — (slot, counter) pairs that differ;
* ``vote_mismatch`` — (node, class) entries of the vote sums that differ
  after some slot.  A row whose two answers are within ``logit_gap``'s
  limit of each other in the reference's logits may vote for either, so
  the reference's vote moves to the program's pick there first;
* ``rows``, ``tied_rows`` — the valid rows compared, and those near-ties.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("row_mismatch", "logit_gap", "counter_mismatch", "vote_mismatch")


def compare(prog: list, ref: list, tie: float) -> dict:
    rows = gap = counters = votes = tied = n_rows = 0
    moved = None
    for p, r in zip(prog, ref, strict=True):
        valid = r["valid"]
        bad = p["valid"] != valid
        for k in ("node_id", "deadline", "cache_hit"):
            bad |= np.asarray(p[k]).astype(np.int64) != r[k]
        rows += int(bad.sum())
        both = valid & ~bad
        n_rows += int(both.sum())
        pl, rl = p["logits"][both], r["logits"][both]
        scale = np.maximum(np.max(np.abs(rl), axis=-1), 1.0)
        if len(rl):
            gap = max(gap, float(np.max(np.max(np.abs(pl - rl), axis=-1)
                                        / scale)))
        counters += int(np.sum(np.asarray(p["counters"]) != r["counters"]))

        pick_p, pick_r = np.argmax(pl, axis=-1), np.argmax(rl, axis=-1)
        near = ((pick_p != pick_r)
                & (np.take_along_axis(rl, pick_r[:, None], -1)[:, 0]
                   - np.take_along_axis(rl, pick_p[:, None], -1)[:, 0]
                   <= tie * scale))
        tied += int(near.sum())
        if moved is None:
            moved = np.zeros_like(r["votes"])
        nodes = r["node_id"][both][near]
        np.add.at(moved, (nodes, pick_r[near]), -1)
        np.add.at(moved, (nodes, pick_p[near]), 1)
        votes += int(np.sum(np.asarray(p["votes"]) != r["votes"] + moved))
    return {"row_mismatch": rows, "logit_gap": gap,
            "counter_mismatch": counters, "vote_mismatch": votes,
            "rows": n_rows, "tied_rows": tied}


def judge(numbers: dict, limits: dict) -> tuple[bool, list]:
    """``correct`` and the list of (name, value, limit) compared: the
    numbers that ``limits`` lists, each at most its limit."""
    unknown = set(limits) - set(NUMBERS)
    if unknown:
        raise KeyError(f"limits name unknown numbers {sorted(unknown)}")
    rows = [(k, numbers[k], limits[k]) for k in NUMBERS if k in limits]
    return all(v <= lim for _, v, lim in rows), rows
