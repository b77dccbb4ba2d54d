"""Plain reference of one Seeker sensor node, written from the paper's
decision flow (Fig. 8) and the configuration alone.

It imports nothing of the program.  One node at a time, one slot at a time:
correlation against the signature bank, the strict store-and-execute ladder
(spend <= stored + harvested), the 16-bit fake-quantized on-node DNN, the
per-channel k-means coreset, the importance-sampling coreset, the
supercapacitor, brown-out hysteresis, and the host's recovery and
full-precision DNN.  Random draws follow the same key discipline as the
system it checks (node ``i`` starts from ``fold_in(key, i)``; each running
slot splits its key into carry, sensor and host keys; a browned-out node
keeps its key).

Storage and arithmetic are float32; ``precision`` is the matrix
products' precision (:func:`mm`).  The reference runs at the precision the
configuration states (``highest``: full float32 products).  The next lower
precision (``high``: three bfloat16 passes) is the precision control,
which must come out as not correct.  Both are spelled out in
:func:`mm`, so they read the same on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

D0, D1, D2, D3, D4, DEFER = 0, 1, 2, 3, 4, 5
LATENT = 16          # generator noise width
KMEANS_ITERS = 4
CAP_UJ = 200.0       # supercapacitor capacity
CHARGE_EFF = 0.8     # charging efficiency of stored surplus


def init_weights(key, model: dict):
    """Random classifier and generator weights from one key: the classifier
    (two conv/pool stages, dense, head) and the recovery generator MLP.
    Biases are drawn too, so that every add is exercised."""
    t, c = model["window"], model["channels"]
    kk, c1, c2, hid, n_cls = (model["kernel"], model["conv1"],
                              model["conv2"], model["hidden"],
                              model["n_classes"])
    flat = (t // 4) * c2
    ks = jax.random.split(key, 16)

    def w(i, shape, fan_in):
        return jax.random.normal(ks[i], shape) / jnp.sqrt(fan_in)

    def b(i, n):
        return 0.1 * jax.random.normal(ks[i], (n,))

    cls = {"conv1_w": w(0, (kk, c, c1), kk * c), "conv1_b": b(1, c1),
           "conv2_w": w(2, (kk, c1, c2), kk * c1), "conv2_b": b(3, c2),
           "dense_w": w(4, (flat, hid), flat), "dense_b": b(5, hid),
           "head_w": w(6, (hid, n_cls), hid), "head_b": b(7, n_cls)}
    g_in, g_hid = LATENT + 2 * c, model["gen_hidden"]
    gen = (w(8, (g_in, g_hid), g_in), b(9, g_hid),
           w(10, (g_hid, g_hid), g_hid), b(11, g_hid),
           w(12, (g_hid, t * c), g_hid), b(13, t * c))
    return cls, gen


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def _full(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _bf16_split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def mm(a, b, precision: str):
    """``a @ b`` in float32 at ``precision``: ``highest`` is full float32
    products; ``high`` is three bfloat16 passes, hi·hi + hi·lo + lo·hi of
    each operand's split into a bfloat16 head and a bfloat16 remainder."""
    if precision == "highest":
        return _full(a, b)
    if precision == "high":
        ah, al = _bf16_split(a)
        bh, bl = _bf16_split(b)
        return _full(ah, bh) + (_full(ah, bl) + _full(al, bh))
    raise ValueError(f"unknown precision {precision!r}; options: highest, "
                     f"high")


def conv_same(x, w, b, prec: str):
    """x (T, Cin), w (K, Cin, Cout): 'same' 1-D convolution as a sum of
    shifted products."""
    k = w.shape[0]
    lo = (k - 1) // 2
    xp = jnp.pad(x, ((lo, k - 1 - lo), (0, 0)))
    t = x.shape[0]
    out = sum(mm(xp[j:j + t], w[j], prec) for j in range(k))
    return out + b


def maxpool2(x):
    return jnp.max(x.reshape(x.shape[0] // 2, 2, x.shape[1]), axis=1)


def fake_quant(x, bits: int):
    """Symmetric per-tensor quantize-dequantize."""
    qmax = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-9) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def classifier(p, x, prec: str, bits: int | None = None):
    """(T, C) window -> (n_classes,) logits; with ``bits`` the weights and
    activations are fake-quantized (the node's crossbar DNN)."""
    q = (lambda v: v) if bits is None else (lambda v: fake_quant(v, bits))
    h = jax.nn.relu(conv_same(q(x), q(p["conv1_w"]), p["conv1_b"], prec))
    h = q(maxpool2(h))
    h = jax.nn.relu(conv_same(h, q(p["conv2_w"]), p["conv2_b"], prec))
    h = q(maxpool2(h)).reshape(-1)
    h = jax.nn.relu(mm(h, q(p["dense_w"]), prec) + p["dense_b"])
    return mm(h, q(p["head_w"]), prec) + p["head_b"]


def correlations(window, bank):
    """Mean over channels of the Pearson correlation of the window with
    each signature: (T, C) x (L, T, C) -> (L,)."""
    wm = window - jnp.mean(window, axis=0)
    sm = bank - jnp.mean(bank, axis=1, keepdims=True)
    num = jnp.sum(sm * wm[None], axis=1)
    den = (jnp.sqrt(jnp.sum(wm * wm, axis=0))[None]
           * jnp.sqrt(jnp.sum(sm * sm, axis=1)))
    return jnp.mean(num / jnp.maximum(den, 1e-9), axis=-1)


def kmeans(points, k: int, prec: str):
    """Lloyd's k-means, strided init, fixed iterations: (centers, radii,
    counts)."""
    n = points.shape[0]
    centers = points[(jnp.arange(k) * n) // k]

    def dist2(c):
        return jnp.sum((points[:, None, :] - c[None]) ** 2, axis=-1)

    for _ in range(KMEANS_ITERS):
        onehot = jax.nn.one_hot(jnp.argmin(dist2(centers), axis=1), k,
                                dtype=points.dtype)
        counts = jnp.sum(onehot, axis=0)
        sums = mm(onehot.T, points, prec)
        centers = jnp.where(counts[:, None] > 0,
                            sums / jnp.maximum(counts[:, None], 1.0),
                            centers)
    d2 = dist2(centers)
    assign = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(assign, k, dtype=points.dtype)
    counts = jnp.sum(onehot, axis=0).astype(jnp.int32)
    dist = jnp.sqrt(jnp.take_along_axis(d2, assign[:, None], axis=1)[:, 0])
    return centers, jnp.max(onehot * dist[:, None], axis=0), counts


def channel_coresets(window, k: int, prec: str):
    """Per-channel (time, value) clustering coresets."""
    t = window.shape[0]

    def one(col):
        ptp = jnp.maximum(jnp.max(col) - jnp.min(col), 1e-6)
        tc = jnp.linspace(0.0, 1.0, t, dtype=col.dtype) * ptp
        return kmeans(jnp.stack([tc, col], axis=-1), k, prec)

    return jax.vmap(one, in_axes=1)(window)


def sampling_coreset(window, m: int, key):
    """Importance sampling without replacement (Gumbel top-m): sorted
    indices, their values, and the window's mean and variance."""
    t = window.shape[0]
    det = window - jnp.mean(window, axis=0, keepdims=True)
    mag = jnp.sum(jnp.abs(det), axis=-1)
    spec = jnp.abs(jnp.fft.rfft(det, axis=0))
    env = jnp.sum(jnp.abs(jnp.fft.irfft(spec * (spec > jnp.median(spec)),
                                        n=t, axis=0)), axis=-1)
    w = mag + env
    w = w / jnp.maximum(jnp.sum(w), 1e-9)
    w = 0.75 * w + 0.25 * jnp.full((t,), 1.0 / t)
    u = jax.random.uniform(key, (t,), minval=1e-9, maxval=1.0)
    scores = jnp.log(jnp.maximum(w, 1e-12)) - jnp.log(-jnp.log(u))
    idx = jnp.sort(jax.lax.top_k(scores, m)[1])
    return idx, window[idx], jnp.mean(window, axis=0), jnp.var(window, axis=0)


def recover_cluster(centers, radii, counts, key, t: int):
    """Per channel: ``count`` points spread in each cluster's ball (radius
    uniform in [0, r]), sorted by time and resampled onto the T grid."""
    c = centers.shape[0]

    def one(cen, rad, cnt, kk):
        k = cen.shape[0]
        total = jnp.maximum(jnp.sum(cnt), 1)
        pos = (jnp.arange(t) * total) // t
        cl = jnp.clip(jnp.searchsorted(jnp.cumsum(cnt), pos, side="right"),
                      0, k - 1)
        knorm, kdir = jax.random.split(kk)
        dirs = jax.random.normal(kdir, (t, 2))
        dirs = dirs / jnp.maximum(jnp.linalg.norm(dirs, axis=-1,
                                                  keepdims=True), 1e-9)
        offs = dirs * jax.random.uniform(knorm, (t, 1))
        pts = cen[cl] + offs * rad[cl][:, None]
        pts = pts[jnp.argsort(pts[:, 0])]
        src = (pts[:, 0] - pts[0, 0]) / jnp.maximum(pts[-1, 0] - pts[0, 0],
                                                    1e-9)
        return interp(jnp.linspace(0.0, 1.0, t), src, pts[:, 1])

    return jax.vmap(one)(centers, radii, counts,
                         jax.random.split(key, c)).T


def interp(x, xp, fp):
    """Piecewise-linear interpolation, clamped at both ends (numpy's
    ``interp``)."""
    i = jnp.clip(jnp.searchsorted(xp, x, side="right"), 1, xp.shape[0] - 1)
    dx = xp[i] - xp[i - 1]
    flat = jnp.abs(dx) <= 1.4e-14
    f = jnp.where(flat, fp[i - 1],
                  fp[i - 1] + ((x - xp[i - 1]) / jnp.where(flat, 1, dx))
                  * (fp[i] - fp[i - 1]))
    f = jnp.where(x < xp[0], fp[0], f)
    return jnp.where(x > xp[-1], fp[-1], f)


def recover_sampled(gen, idx, vals, mean, var, key, t: int, prec: str):
    """The generator fills the window from its moments; the transmitted
    samples are written back at their indices."""
    w1, b1, w2, b2, w3, b3 = gen
    noise = jax.random.normal(key, (LATENT,))
    h = jnp.concatenate([noise, mean, jnp.sqrt(jnp.maximum(var, 0.0))])
    h = jnp.tanh(mm(h, w1, prec) + b1)
    h = jnp.tanh(mm(h, w2, prec) + b2)
    out = (mm(h, w3, prec) + b3).reshape(t, -1)
    return out.at[idx].set(vals)


# ---------------------------------------------------------------------------
# One node, one slot
# ---------------------------------------------------------------------------

def ladder_costs(costs: dict, scale: float):
    """µJ per decision D0..D4 and DEFER (paper Table 2)."""
    c = {k: v * scale for k, v in costs.items()}
    return (c["sense"] + c["tx_result"], c["dnn_full"] + c["tx_result"],
            c["dnn16"] + c["tx_result"],
            c["sense"] + c["coreset_cluster"] + c["tx_coreset"],
            c["sense"] + c["coreset_sampling"] + c["tx_coreset"], c["sense"])


def node_slot(cfg: dict, prec: str, weights, bank, carry, window,
              harvested):
    """Advance one node by one slot with matrix products at ``prec``.
    ``carry`` = (stored, browned, key).  Returns the new carry and the
    slot's trace."""
    stored, browned, key = carry
    t, c = window.shape
    k_max, m = cfg["k_max"], cfg["m_samples"]
    cls, gen = weights
    costs = jnp.asarray(ladder_costs(cfg["costs"], cfg["cost_scale"]),
                        jnp.float32)
    alive = ~browned

    ks = jax.random.split(key, 3)
    corr = correlations(window, bank)
    budget = stored + harvested
    memo = (jnp.max(corr) >= cfg["corr_threshold"]) & (budget >= costs[D0])
    offload = jnp.where(budget >= costs[D3], D3,
                        jnp.where(budget >= costs[D4], D4, DEFER))
    decision = jnp.where(memo, D0, jnp.where(budget >= costs[D2], D2,
                                             offload)).astype(jnp.int32)
    spend = costs[decision]
    spend = jnp.where(budget >= spend, spend, jnp.zeros_like(spend))

    dnn_logits = classifier(cls, window, prec, cfg["quant_bits"])
    dnn_label = jnp.argmax(dnn_logits)
    label = jnp.where(decision == D0, jnp.argmax(corr),
                      jnp.where(decision == D2, dnn_label, -1))
    centers, radii, counts = channel_coresets(window, k_max, prec)
    idx, vals, mean, var = sampling_coreset(window, m, ks[1])

    payload = jnp.asarray(
        [2.0, 2.0, 2.0, (k_max * 3 + -(-k_max // 2)) * c,
         m * (1 + 2 * c) + 4 * c, 0.0], jnp.float32)[decision]

    direct = jnp.minimum(spend, harvested)
    ran = jnp.clip(stored + CHARGE_EFF * (harvested - direct)
                   - (spend - direct), 0.0, CAP_UJ)
    trickle = jnp.clip(stored + CHARGE_EFF * harvested, 0.0, CAP_UJ)
    new_stored = jnp.where(alive, ran, trickle)
    new_browned = jnp.where(browned, new_stored < cfg["restart_uj"],
                            new_stored < cfg["off_uj"])

    k1, k2 = jax.random.split(ks[2])
    win_c = recover_cluster(centers, radii, counts, k1, t)
    win_s = recover_sampled(gen, idx, vals, mean, var, k2, t, prec)
    onehot = jax.nn.one_hot(label, cls["head_b"].shape[0]) * 8.0
    logits = jnp.where(decision == D3, classifier(cls, win_c, prec),
                       jnp.where(decision == D4, classifier(cls, win_s, prec),
                                 jnp.where(decision == DEFER,
                                           jnp.zeros_like(onehot), onehot)))
    zero = jnp.zeros_like(logits)
    trace = {"decision": jnp.where(alive, decision, DEFER),
             "payload": jnp.where(alive, payload, 0.0),
             "stored": new_stored,
             "k": jnp.where(alive, k_max, 0).astype(jnp.int32),
             "logits": jnp.where(alive, logits, zero),
             "alive": alive,
             "label": jnp.where(alive, label, -1).astype(jnp.int32),
             "corr": corr, "dnn_logits": dnn_logits}
    new_key = jnp.where(alive, ks[0], key)
    return (new_stored, new_browned, new_key), trace


@functools.lru_cache(maxsize=8)
def _segment_fn(cfg_items: tuple, prec: str):
    cfg = dict(cfg_items)
    cfg["costs"] = dict(cfg["costs"])

    def segment(weights, bank, carry, windows, harvest):
        """windows (K, S, T, C), harvest (K, S) for K nodes over S slots."""
        def one_node(carry_n, win_n, harv_n):
            return jax.lax.scan(
                lambda cr, x: node_slot(cfg, prec, weights, bank, cr, x[0],
                                        x[1]),
                carry_n, (win_n, harv_n))
        carry, tr = jax.vmap(one_node)(carry, windows, harvest)
        return carry, jax.tree_util.tree_map(lambda a: jnp.swapaxes(a, 0, 1),
                                             tr)   # (S, K, ...)

    return jax.jit(segment)


def freeze(cfg: dict) -> tuple:
    """Hashable form of a node configuration (the keys the reference
    reads)."""
    keys = ("k_max", "m_samples", "quant_bits", "corr_threshold",
            "cost_scale", "off_uj", "restart_uj")
    return tuple((k, cfg[k]) for k in keys) + (
        ("costs", tuple(sorted(cfg["costs"].items()))),)


def init_carry(cfg: dict, fleet_key, nodes):
    """Boot state of the given nodes: initial charge, boot-time brown-out
    flag, and node ``i``'s key ``fold_in(fleet_key, i)``."""
    k = len(nodes)
    stored = jnp.full((k,), cfg["initial_uj"], jnp.float32)
    keys = jax.vmap(lambda i: jax.random.fold_in(fleet_key, i))(
        jnp.asarray(nodes))
    return stored, stored < cfg["off_uj"], keys


def run_segment(cfg: dict, weights, bank, carry, windows, harvest,
                precision: str):
    """Run K nodes over one segment of slots with matrix products at
    ``precision`` (see :func:`mm`); returns (carry, traces)."""
    return _segment_fn(freeze(cfg), precision)(weights, bank, carry, windows,
                                               harvest)
