"""The benchmark's own input generators: sensor windows, labels, signature
banks and harvest traces, all pure functions of a PRNG key.

These are copies of the program's generators (``repro.data.sensors`` and
``repro.core.energy``) kept with the benchmark, so that a change to the
program cannot move the yardstick.  One departure: a node's stream is drawn
per (node, slot) from folded keys instead of one ``split`` over the whole
stream, so any slice of slots can be generated on its own and set-up never
holds more than one segment's temporaries.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SLOT_SECONDS = 0.6
EH_SOURCES = ("rf", "wifi", "piezo", "solar")


# ---------------------------------------------------------------------------
# HAR windows (MHEALTH-like), as in repro.data.sensors
# ---------------------------------------------------------------------------

def _har_class_params(n_classes: int, channels: int, t: int):
    k = jax.random.PRNGKey(1234)
    k1, k3, k4 = jax.random.split(k, 3)
    lo, hi = int(0.10 * t), int(0.90 * t)
    pos = jnp.round(lo + (hi - lo) * jax.random.uniform(k1, (n_classes, 3)))
    width = 0.8 + 1.2 * jax.random.uniform(k3, (n_classes, 3))
    amp = 0.45 + 0.25 * jax.random.uniform(k4, (n_classes, 3, channels))
    sign = jnp.sign(jax.random.normal(jax.random.fold_in(k4, 1),
                                      (n_classes, 3, channels)))
    return pos, width, amp * sign


def har_window(key, label, t: int = 60, channels: int = 3,
               n_classes: int = 12, fs: float = 50.0, noise: float = 0.12):
    """One (T, C) window of activity ``label``."""
    pos, width, amp = _har_class_params(n_classes, channels, t)
    kp, kn, ka, kj = jax.random.split(key, 4)
    tgrid = jnp.arange(t) / fs
    idx = jnp.arange(t, dtype=jnp.float32)
    n_harm = 14
    hfreq = 0.8 * (1 + jnp.arange(n_harm, dtype=jnp.float32) * 0.72)
    hamp = 1.0 / (1.0 + 0.28 * jnp.arange(n_harm, dtype=jnp.float32))
    hphase = (2.3 * jnp.arange(n_harm)[:, None]
              + 0.35 * jax.random.normal(kp, (n_harm, channels)))
    base = jnp.sum(hamp[None, :, None]
                   * jnp.sin(2 * jnp.pi * hfreq[None, :, None]
                             * tgrid[:, None, None] + hphase[None]),
                   axis=1) / 2.0
    jit = jax.random.randint(kj, (3,), -1, 2).astype(jnp.float32)
    amp_jit = 1.0 + 0.15 * jax.random.normal(ka, (channels,))
    sig = base
    for e in range(3):
        ev = jnp.exp(-0.5 * ((idx - pos[label, e] - jit[e])
                             / width[label, e]) ** 2)
        sig = sig + ev[:, None] * amp[label, e] * amp_jit
    return sig + noise * jax.random.normal(kn, (t, channels))


def har_signatures(t: int = 60, channels: int = 3, n_classes: int = 12):
    """Noise-free per-class traces: the node's memoization bank."""
    keys = jax.random.split(jax.random.PRNGKey(7), n_classes)
    return jnp.stack([
        har_window(keys[c], jnp.asarray(c), t, channels, n_classes,
                   noise=0.0) for c in range(n_classes)])


# ---------------------------------------------------------------------------
# Bearing vibration windows (CWRU-like), as in repro.data.sensors
# ---------------------------------------------------------------------------

_FAULT_FREQ = (0.0, 3.585, 5.415, 4.7135, 3.585, 5.415, 4.7135, 3.585,
               5.415, 4.7135)
_FAULT_SEV = (0.0, 0.6, 0.6, 0.6, 1.2, 1.2, 1.2, 2.0, 2.0, 2.0)


def bearing_window(key, label, t: int = 120, rpm_hz: float = 15.0,
                   fs: float = 1200.0, noise: float = 0.15):
    """(T, 1) window: class 0 healthy, 1-9 fault type x severity."""
    kp, kn, kj = jax.random.split(key, 3)
    tgrid = jnp.arange(t) / fs
    phase = jax.random.uniform(kp, maxval=2 * jnp.pi)
    base = (jnp.sin(2 * jnp.pi * rpm_hz * tgrid + phase)
            + 0.3 * jnp.sin(2 * jnp.pi * 2 * rpm_hz * tgrid + 1.7 * phase))
    f_def = jnp.asarray(_FAULT_FREQ)[label] * rpm_hz
    sev = jnp.asarray(_FAULT_SEV)[label]
    jitter = 1.0 + 0.05 * jax.random.normal(kj, ())
    impulses = sev * jnp.cos(jnp.pi * f_def * jitter * tgrid + phase) ** 4
    ring = sev * 0.4 * jnp.sin(2 * jnp.pi * 5.1 * rpm_hz * tgrid) * impulses
    sig = base + impulses + ring + noise * jax.random.normal(kn, (t,))
    return sig[:, None]


def bearing_signatures(t: int = 120, n_classes: int = 10):
    """Noise-free per-class bearing traces at one fixed phase each."""
    keys = jax.random.split(jax.random.PRNGKey(7), n_classes)
    return jnp.stack([bearing_window(keys[c], jnp.asarray(c), t, noise=0.0)
                      for c in range(n_classes)])


STREAMS = {
    "har": (lambda k, lab, t, c, n_cls: har_window(k, lab, t, c, n_cls),
            lambda t, c, n_cls: har_signatures(t, c, n_cls)),
    "bearing": (lambda k, lab, t, c, n_cls: bearing_window(k, lab, t),
                lambda t, c, n_cls: bearing_signatures(t, n_cls)),
}


def stream_labels(key, n_nodes: int, horizon: int, n_classes: int,
                  dwell: int):
    """(N, H) int32 labels: each node's activity changes every ``dwell``
    slots (the paper's temporal continuity)."""
    n_seg = -(-horizon // dwell)

    def one(i):
        seg = jax.random.randint(jax.random.fold_in(
            jax.random.fold_in(key, i), 0), (n_seg,), 0, n_classes)
        return jnp.repeat(seg, dwell)[:horizon]

    return jax.vmap(one)(jnp.arange(n_nodes))


def stream_windows(kind: str, key, labels, slots, t: int, channels: int,
                   n_classes: int):
    """(N, len(slots), T, C) windows for the given absolute slot indices;
    node ``i``'s window at slot ``s`` comes from its own folded key, so a
    segment is the same whichever way the horizon is cut."""
    window = STREAMS[kind][0]
    n = labels.shape[0]

    def at_slot(s):
        def node(i, lab):
            k = jax.random.fold_in(jax.random.fold_in(
                jax.random.fold_in(key, i), 1), s)
            return window(k, lab, t, channels, n_classes)
        return jax.vmap(node)(jnp.arange(n), labels[:, s])   # (N, T, C)

    out = jax.lax.map(at_slot, jnp.asarray(slots))           # (S, N, T, C)
    return jnp.moveaxis(out, 0, 1)


def signatures(kind: str, t: int, channels: int, n_classes: int):
    return STREAMS[kind][1](t, channels, n_classes)


# ---------------------------------------------------------------------------
# Harvest traces (µJ per slot), as in repro.core.energy
# ---------------------------------------------------------------------------

def _bursty(key, n: int, mean_power_uw: float, burstiness: float,
            period: float):
    k1, k2 = jax.random.split(key)
    t = jnp.arange(n) * SLOT_SECONDS
    base = 0.5 * (1.0 + jnp.sin(2 * jnp.pi * t / period))
    noise = jnp.exp(burstiness * jax.random.normal(k1, (n,))
                    - 0.5 * burstiness ** 2)
    dropout = (jax.random.uniform(k2, (n,)) > 0.15).astype(jnp.float32)
    return mean_power_uw * base * noise * dropout * SLOT_SECONDS


def harvest_trace(key, n: int, source: str):
    """µJ harvested in each of ``n`` slots for one source modality."""
    if source == "rf":
        return _bursty(key, n, mean_power_uw=45.0, burstiness=0.9,
                       period=40.0)
    if source == "wifi":
        return _bursty(key, n, mean_power_uw=70.0, burstiness=1.2,
                       period=15.0)
    if source == "piezo":
        k1, k2 = jax.random.split(key)
        active = (jax.random.uniform(k1, (n,)) > 0.35).astype(jnp.float32)
        jitter = 1.0 + 0.3 * jax.random.normal(k2, (n,))
        return jnp.maximum(250.0 * active * jitter, 0.0) * SLOT_SECONDS
    if source == "solar":
        k1, _ = jax.random.split(key)
        t = jnp.arange(n) * SLOT_SECONDS
        diurnal = jnp.maximum(jnp.sin(2 * jnp.pi * t / (n * SLOT_SECONDS)),
                              0.0)
        clouds = 0.6 + 0.4 * jax.random.uniform(k1, (n,))
        return 800.0 * diurnal * clouds * SLOT_SECONDS
    raise ValueError(f"unknown harvest source {source!r}; "
                     f"options: {EH_SOURCES}")


def source_assignment(n_nodes: int, sources) -> np.ndarray:
    """Node -> harvest-modality index: round-robin over ``sources``."""
    return np.arange(n_nodes) % len(tuple(sources))


def harvest_traces(key, n_nodes: int, n_slots: int, sources):
    """(N, S) per-node harvest; node ``i`` draws from ``fold_in(key, i)``."""
    sources = tuple(sources)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_nodes))
    out = jnp.zeros((n_nodes, n_slots), jnp.float32)
    node_src = source_assignment(n_nodes, sources)
    for si, src in enumerate(sources):
        sel = np.nonzero(node_src == si)[0]
        if sel.size == 0:
            continue
        tr = jax.vmap(lambda k: harvest_trace(k, n_slots, src))(keys[sel])
        out = out.at[sel].set(tr)
    return out
