"""Plain reference of the Seeker host tier, written from the paper's host
side (arXiv 2408.14379, sections 3.2.2-3.3) and the wire format alone.

It imports nothing of the program.  Given the ingest lanes a host was fed
(one dict of payload columns per slot, as plain arrays), the boot state and
the host's numbers, it replays slot by slot:

* the ring queue: a lane whose masked rows all fit lands in the free slots
  in ring order from the write cursor (the i-th row in the i-th free slot);
  a lane that does not fit is pushed row by row, each into the first free
  slot from the cursor or, when none is free, over the resident with the
  latest deadline (ties: lowest slot) if the row's deadline is earlier,
  and every such row counts one overflow drop;
* expiry (``deadline < now`` is a miss) and the EDF pop: the live entries
  by (deadline, slot index), then, to fill a short batch, the empty slots
  by index (padding rows, ``valid`` false);
* the payload signature (two 32-bit mixes of the payload's words) and the
  FIFO recovery memo keyed by it;
* each payload's recovery key ``fold_in(fold_in(base_key, sig0), sig1)``;
* the wire decode (int16 and int8 codes with float ranges), the cluster and
  GAN recoveries and the full-precision DNN (``bench/reference.py``);
* the per-node vote ensemble.

The queue and memo work on integers only: a memo hit names the row whose
logits it returns, and every row's logits are computed afterwards in bulk,
with matrix products at ``precision`` (``bench/reference.py``'s ``mm``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import classifier, recover_cluster, recover_sampled

CLUSTER, SAMPLING = 0, 1
NO_DEADLINE = np.iinfo(np.int32).max
# the payload's columns in the order their words are hashed
COLUMNS = ("kind", "c_codes", "r_codes", "n_codes", "c_lo", "c_hi", "c_rhi",
           "s_idx", "s_codes", "s_lo", "s_hi", "s_mean", "s_var", "task")
# counters after each slot, in this order
COUNTERS = ("served", "deadline_misses", "drops_overflow", "backlog",
            "cache_hits", "cache_misses")
_MUL_A, _MUL_B = np.uint32(2654435761), np.uint32(2246822519)
_SEEDS = (np.uint32(2654435761), np.uint32(2246822519))
_CHUNK = 4096


# ---------------------------------------------------------------------------
# Payload signature
# ---------------------------------------------------------------------------

def _words(lane: dict) -> np.ndarray:
    """(A, W) uint32 words of each row: float columns by their bits,
    integer columns as two's-complement 32-bit words."""
    cols = []
    for name in COLUMNS:
        x = np.asarray(lane[name])
        x = x.reshape(x.shape[0], -1)
        if np.issubdtype(x.dtype, np.floating):
            cols.append(x.astype(np.float32).view(np.uint32))
        else:
            cols.append(x.astype(np.int64).astype(np.uint32))
    return np.concatenate(cols, axis=1)


def _mix(words: np.ndarray, seed: np.uint32) -> np.ndarray:
    idx = np.arange(words.shape[1], dtype=np.uint32)
    mult = idx * _MUL_A + seed
    mult = (mult ^ (mult >> np.uint32(15))) * _MUL_B
    mult = (mult ^ (mult >> np.uint32(13))) | np.uint32(1)
    h = np.sum((words ^ (words >> np.uint32(16))) * mult[None], axis=1,
               dtype=np.uint32)
    h = (h ^ (h >> np.uint32(15))) * _MUL_B
    return h ^ (h >> np.uint32(13))


def signatures(lane: dict) -> np.ndarray:
    """(A, 2) uint32 signature of every row of a lane."""
    w = _words(lane)
    return np.stack([_mix(w, s) for s in _SEEDS], axis=1)


# ---------------------------------------------------------------------------
# Ring queue, EDF pop, recovery memo
# ---------------------------------------------------------------------------

class _Queue:
    """Slot arrays of the ring queue.  A slot holds where its payload came
    from (lane, row) instead of the payload itself."""

    def __init__(self, cap: int):
        self.cap = cap
        self.src = np.zeros((cap, 2), np.int64)
        self.node = np.zeros(cap, np.int64)
        self.arrival = np.zeros(cap, np.int64)
        self.deadline = np.full(cap, NO_DEADLINE, np.int64)
        self.valid = np.zeros(cap, bool)
        self.cursor = 0
        self.drops = 0

    def _put(self, slot, src, node, now, deadline):
        self.src[slot] = src
        self.node[slot], self.arrival[slot] = node, now
        self.deadline[slot], self.valid[slot] = deadline, True

    def push(self, lane_id: int, rows: np.ndarray, nodes: np.ndarray,
             now: int, deadline: int):
        free = np.flatnonzero(~self.valid)
        if len(rows) <= len(free):
            targets = free[np.argsort((free - self.cursor) % self.cap,
                                      kind="stable")][:len(rows)]
            for slot, r, n in zip(targets, rows, nodes):
                self._put(slot, (lane_id, r), n, now, deadline)
            if len(rows):
                self.cursor = int(targets[-1] + 1) % self.cap
            return
        for r, n in zip(rows, nodes):
            free = np.flatnonzero(~self.valid)
            if len(free):
                slot = free[np.argmin((free - self.cursor) % self.cap)]
            else:
                self.drops += 1
                slot = int(np.argmax(self.deadline))
                if self.deadline[slot] <= deadline:
                    continue
            self._put(slot, (lane_id, r), n, now, deadline)
            self.cursor = (int(slot) + 1) % self.cap

    def expire(self, now: int) -> int:
        missed = self.valid & (self.deadline < now)
        self.valid &= ~missed
        return int(missed.sum())

    def edf_order(self) -> np.ndarray:
        """Live slots by (deadline, slot index)."""
        live = np.flatnonzero(self.valid)
        return live[np.argsort(self.deadline[live], kind="stable")]


class _Memo:
    """FIFO memo of signatures; an entry names the row whose logits it
    holds."""

    def __init__(self, cap: int):
        self.cap = cap
        self.sig = np.zeros((cap, 2), np.uint32)
        self.row = np.zeros(cap, np.int64)
        self.valid = np.zeros(cap, bool)
        self.cursor = 0
        self.hits = self.misses = 0

    def lookup(self, sigs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(hit, row of the first matching entry) of each signature."""
        match = self.valid[None] & np.all(self.sig[None] == sigs[:, None],
                                          axis=-1)
        return match.any(axis=1), self.row[np.argmax(match, axis=1)]

    def insert(self, sig: np.ndarray, row: int):
        pos = self.cursor % self.cap
        self.sig[pos], self.row[pos], self.valid[pos] = sig, row, True
        self.cursor += 1


# ---------------------------------------------------------------------------
# Recovery and DNN
# ---------------------------------------------------------------------------

def _decode_cluster(p):
    span = p["c_hi"] - p["c_lo"]
    centers = ((p["c_codes"].astype(jnp.float32) + 32768.0) / 65535.0
               * span + p["c_lo"])
    radii = (p["r_codes"].astype(jnp.float32) + 128.0) / 255.0 * p["c_rhi"]
    return centers, radii, p["n_codes"].astype(jnp.int32)


def _decode_samples(p):
    vals = ((p["s_codes"].astype(jnp.float32) + 32768.0) / 65535.0
            * (p["s_hi"] - p["s_lo"]) + p["s_lo"])
    return p["s_idx"].astype(jnp.int32), vals, p["s_mean"], p["s_var"]


@functools.lru_cache(maxsize=8)
def _logits_fn(kind: int, t: int, prec: str):
    def one(weights, p, key):
        cls, gen = weights
        if kind == CLUSTER:
            centers, radii, counts = _decode_cluster(p)
            win = recover_cluster(centers, radii, counts, key, t)
        else:
            idx, vals, mean, var = _decode_samples(p)
            win = recover_sampled(gen, idx, vals, mean, var, key, t, prec)
        return classifier(cls, win, prec)
    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0)))


def _row_logits(weights, rows: dict, keys, kind: int, t: int,
                prec: str) -> np.ndarray:
    """Logits of payload rows of one kind, in chunks of a fixed size."""
    n = len(keys)
    fn = _logits_fn(kind, t, prec)
    out = []
    for lo in range(0, n, _CHUNK):
        sel = np.arange(lo, lo + _CHUNK).clip(max=n - 1)
        part = {k: jnp.asarray(v[sel]) for k, v in rows.items()}
        out.append(np.asarray(fn(weights, part, keys[sel]))[:min(_CHUNK,
                                                                  n - lo)])
    return np.concatenate(out)


def _keys(base_key, sigs: np.ndarray):
    return jax.vmap(lambda s: jax.random.fold_in(
        jax.random.fold_in(base_key, s[0]), s[1]))(jnp.asarray(sigs))


# ---------------------------------------------------------------------------
# The replay
# ---------------------------------------------------------------------------

def replay(lanes: list, host: dict, n_nodes: int, weights, base_key,
           t: int, precision: str) -> list[dict]:
    """Serve ``lanes`` (per slot: the payload columns of ``COLUMNS`` with a
    leading lane axis, plus ``node_id`` and ``mask``) from the boot state.
    ``host`` holds ``batch_size``, ``batches_per_slot``, ``queue_capacity``,
    ``cache_capacity`` and ``qos_slots``.  Returns per slot the served rows
    (``node_id``, ``deadline``, ``valid``, ``cache_hit``, ``logits``), the
    ``counters`` of :data:`COUNTERS` after the slot, and the cumulative
    ``votes`` (n_nodes, L)."""
    b, nb = host["batch_size"], host["batches_per_slot"]
    queue, memo = _Queue(host["queue_capacity"]), _Memo(host["cache_capacity"])
    sigs = [signatures(lane) for lane in lanes]
    n_cls = weights[0]["head_b"].shape[0]
    served = missed = 0
    slots, fresh = [], []          # fresh: (lane, row) of each computed row
    for now, lane in enumerate(lanes):
        mask = np.asarray(lane["mask"])
        rows = np.flatnonzero(mask)
        queue.push(now, rows, np.asarray(lane["node_id"])[rows], now,
                   now + host["qos_slots"])
        missed += queue.expire(now)
        order, at = queue.edf_order(), 0
        out = {k: [] for k in ("node_id", "deadline", "valid", "cache_hit",
                               "row")}
        for _ in range(nb):
            live = order[at:at + b]
            at += len(live)
            pad = np.flatnonzero(~queue.valid)[:b - len(live)]
            take = np.concatenate([live, pad])
            out["node_id"].append(queue.node[take])
            out["deadline"].append(queue.deadline[take])
            out["valid"].append(np.arange(b) < len(live))
            hit, row = np.zeros(b, bool), np.full(b, -1)
            bsig = np.asarray([sigs[j][r] for j, r in queue.src[live]],
                              np.uint32).reshape(-1, 2)
            # lookups see the memo as it stood before the batch's inserts
            found, at_row = memo.lookup(bsig)
            for i, s in enumerate(live):
                if found[i]:
                    hit[i], row[i] = True, at_row[i]
                else:
                    row[i] = len(fresh)
                    fresh.append(tuple(queue.src[s]))
                    memo.insert(bsig[i], row[i])
            memo.hits += int(hit.sum())
            memo.misses += len(live) - int(hit.sum())
            served += len(live)
            queue.valid[live] = False
            out["cache_hit"].append(hit)
            out["row"].append(row)
        slot = {k: np.concatenate(v) for k, v in out.items()}
        slot["counters"] = np.asarray(
            [served, missed, queue.drops, int(queue.valid.sum()), memo.hits,
             memo.misses], np.int64)
        slots.append(slot)

    logits = _fresh_logits(lanes, sigs, np.asarray(fresh, np.int64).reshape(
        -1, 2), weights, base_key, t, precision, n_cls)
    votes = np.zeros((n_nodes, n_cls), np.int64)
    for slot in slots:
        valid = slot["valid"]
        lg = np.zeros((len(valid), n_cls), np.float32)
        lg[valid] = logits[slot.pop("row")[valid]]
        slot["logits"] = lg
        np.add.at(votes, (slot["node_id"][valid],
                          np.argmax(lg[valid], axis=-1)), 1)
        slot["votes"] = votes.copy()
    return slots


def _fresh_logits(lanes, sigs, src, weights, base_key, t, precision,
                  n_cls):
    """Logits of the rows ``src`` ((R, 2) lane and row) of the lanes."""
    out = np.zeros((len(src), n_cls), np.float32)
    if not len(src):
        return out
    cols, sig = {}, np.zeros((len(src), 2), np.uint32)
    for j in np.unique(src[:, 0]):
        at = np.flatnonzero(src[:, 0] == j)
        rows = src[at, 1]
        for k in COLUMNS:
            col = np.asarray(lanes[j][k])
            if k not in cols:
                cols[k] = np.zeros((len(src),) + col.shape[1:], col.dtype)
            cols[k][at] = col[rows]
        sig[at] = sigs[j][rows]
    keys = _keys(base_key, sig)
    for kind in (CLUSTER, SAMPLING):
        sel = np.flatnonzero(cols["kind"] == kind)
        if len(sel):
            rows = {k: v[sel] for k, v in cols.items()}
            out[sel] = _row_logits(weights, rows, keys[sel], kind, t,
                                   precision)
    return out
