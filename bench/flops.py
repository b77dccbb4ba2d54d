"""Operation and byte counts, from shapes alone.

* :func:`path_flops` — FLOPs a simulated node-slot requires for each
  decision of the ladder, at the configuration's published widths.  The
  whole step's FLOPs are the run's own decision histogram times these, so
  the count is the same whatever implements the ladder.
* :func:`signature_corr_cost`, :func:`fake_quant_cost` — the least work of
  the two Pallas kernels on the fleet path, per simulated slot.

A multiply-add counts as 2 FLOPs.
"""
from __future__ import annotations

D0, D1, D2, D3, D4, DEFER = range(6)
KMEANS_ITERS = 4
LATENT = 16


def classifier_flops(m: dict) -> int:
    """One forward of the two-stage 1-D CNN: conv1, conv2, dense, head."""
    t, c, k = m["window"], m["channels"], m["kernel"]
    c1, c2, hid, n_cls = m["conv1"], m["conv2"], m["hidden"], m["n_classes"]
    conv1 = 2 * t * k * c * c1
    conv2 = 2 * (t // 2) * k * c1 * c2
    dense = 2 * (t // 4) * c2 * hid
    head = 2 * hid * n_cls
    return conv1 + conv2 + dense + head


def generator_flops(m: dict) -> int:
    """One forward of the recovery generator MLP."""
    g_in, hid = LATENT + 2 * m["channels"], m["gen_hidden"]
    return 2 * (g_in * hid + hid * hid + hid * m["window"] * m["channels"])


def kmeans_flops(m: dict, k: int) -> int:
    """Per-channel Lloyd on a T-point 2-D cloud: distances (difference,
    square, sum: 3 per coordinate) in every iteration and the final
    assignment, and the per-cluster sums as a (k, T) x (T, 2) product."""
    t, c, d = m["window"], m["channels"], 2
    dist = (KMEANS_ITERS + 1) * t * k * 3 * d
    sums = KMEANS_ITERS * 2 * t * k * d
    return c * (dist + sums)


def corr_flops(m: dict) -> int:
    """Signature correlation of one window against the bank: 2·L·T·C."""
    return 2 * m["n_classes"] * m["window"] * m["channels"]


def path_flops(m: dict, k: int) -> dict:
    """FLOPs per alive node-slot by decision code.  Every alive slot
    correlates against the bank; D1/D2 add one on-node forward, D3 a
    k-means coreset and one host forward, D4 one generator and one host
    forward; D0 and DEFER nothing more."""
    corr, fwd = corr_flops(m), classifier_flops(m)
    return {D0: corr, D1: corr + fwd, D2: corr + fwd,
            D3: corr + kmeans_flops(m, k) + fwd,
            D4: corr + generator_flops(m) + fwd, DEFER: corr}


def step_flops(m: dict, k: int, histogram) -> int:
    """FLOPs of the simulated decisions: histogram[d] alive node-slots took
    decision d."""
    per = path_flops(m, k)
    return int(sum(int(histogram[d]) * per[d] for d in range(len(per))))


def signature_corr_cost(m: dict, n_nodes: int) -> dict:
    """One slot's call: read the (N, T, C) windows and the (L, T, C) bank,
    write (N, L) float32; centre, square-sum and the T-contraction."""
    t, c, n_cls = m["window"], m["channels"], m["n_classes"]
    nbytes = 4 * (n_nodes * t * c + n_cls * t * c + n_nodes * n_cls)
    flops = 2 * n_nodes * n_cls * t * c + 4 * (n_nodes + n_cls) * t * c
    return {"flops": flops, "bytes": nbytes}


def fake_quant_tensors(m: dict, n_nodes: int) -> list[int]:
    """Element counts of one slot's fake-quantized tensors: the four weight
    matrices once, and each node's input window and two pooled maps."""
    t, c, k = m["window"], m["channels"], m["kernel"]
    c1, c2, hid, n_cls = m["conv1"], m["conv2"], m["hidden"], m["n_classes"]
    weights = [k * c * c1, k * c1 * c2, (t // 4) * c2 * hid, hid * n_cls]
    acts = [n_nodes * t * c, n_nodes * (t // 2) * c1, n_nodes * (t // 4) * c2]
    return weights + acts


def fake_quant_cost(m: dict, n_nodes: int) -> dict:
    """One slot's calls: each element read and written as float32, and
    four operations on it (divide, round, clip, multiply)."""
    elems = sum(fake_quant_tensors(m, n_nodes))
    return {"flops": 4 * elems, "bytes": 8 * elems}


def least_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """The least time a call could take on the chip, and which bound sets
    it: the larger of FLOPs over peak FLOP/s and bytes over HBM bytes/s."""
    t_flops = cost["flops"] / peak["bf16_flops"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops,
                                                           "compute")
