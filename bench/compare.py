"""The comparison that decides ``correct`` for the fleet cells.

Inputs are the program's and the reference's traces of the same sampled
nodes over the same slots, as numpy arrays shaped (S, K[, L]).  A node's
trajectory is compared slot by slot up to the first slot where its
decision, alive flag, payload bytes or coreset k differ from the
reference; from there on its energy state has left the reference's, and
only the departure itself counts.

Numbers (a cell compares those its ``bench/limits/<cell>.json`` lists,
each against its own limit), and the counts of slots they rest on:

* ``departed_share`` — share of the sampled nodes whose trajectory departs
  from the reference at some slot (one flip near a threshold costs one
  node, however long its trajectory runs on);
* ``stored_gap`` — widest gap of stored energy, relative to the
  reference's charge (floor 1 µJ), over the slots before each node's
  first mismatch;
* ``label_gap`` — on D0 and D2 slots before the first mismatch, how far
  the reference's score of the label the program sent lies below the
  reference's best score (correlation for D0, quantized-DNN logit for D2);
* ``logit_gap`` — on D3 and D4 slots before the first mismatch, the widest
  gap between the program's and the reference's host logits, relative to
  the reference's largest logit magnitude (floor 1);
* ``label_slots``, ``host_slots`` — the D0/D2 and D3/D4 slots compared.
"""
from __future__ import annotations

import numpy as np

D0, D2, D3, D4 = 0, 2, 3, 4
NUMBERS = ("departed_share", "stored_gap", "label_gap", "logit_gap")


def compare(prog: dict, ref: dict) -> dict:
    dec_p, dec_r = prog["decision"], ref["decision"]
    bad = ((dec_p != dec_r) | (prog["alive"] != ref["alive"])
           | (prog["payload"] != ref["payload"]) | (prog["k"] != ref["k"]))
    # slots before each node's first mismatch
    ok = np.cumsum(bad, axis=0) == 0
    out = {"departed_share": float(bad.any(axis=0).mean())}

    ref_st = ref["stored"]
    gap = np.abs(prog["stored"] - ref_st) / np.maximum(np.abs(ref_st), 1.0)
    out["stored_gap"] = float(np.max(np.where(ok, gap, 0.0)))

    # the label the program sent rides its host logits as one_hot * 8
    label_p = np.argmax(prog["logits"], axis=-1)
    corr, dnn = ref["corr"], ref["dnn_logits"]
    take = lambda a: np.take_along_axis(a, label_p[..., None], -1)[..., 0]
    g0 = corr.max(-1) - take(corr)
    g2 = dnn.max(-1) - take(dnn)
    lab = np.where(dec_r == D0, g0, np.where(dec_r == D2, g2, 0.0))
    out["label_gap"] = float(np.max(np.where(ok, lab, 0.0)))
    out["label_slots"] = int(np.sum(ok & ((dec_r == D0) | (dec_r == D2))))

    host = ok & ((dec_r == D3) | (dec_r == D4))
    lg = (np.max(np.abs(prog["logits"] - ref["logits"]), axis=-1)
          / np.maximum(np.max(np.abs(ref["logits"]), axis=-1), 1.0))
    out["logit_gap"] = float(np.max(np.where(host, lg, 0.0)))
    out["host_slots"] = int(host.sum())
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, list]:
    """``correct`` and the list of (name, value, limit) compared: the
    numbers that ``limits`` lists, each at most its limit."""
    unknown = set(limits) - set(NUMBERS)
    if unknown:
        raise KeyError(f"limits name unknown numbers {sorted(unknown)}")
    rows = [(k, numbers[k], limits[k]) for k in NUMBERS if k in limits]
    return all(v <= lim for _, v, lim in rows), rows
