"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

* the window: the host span named ``bench.window``;
* busy time per device: the union of the op intervals on the device's
  ``XLA Ops`` line, clipped to the window;
* device seconds per op, keyed by the op's own HLO name (the text before
  `` = ``; a Pallas kernel's custom call is named after its kernel), over
  leaf ops only: a ``while`` or ``conditional`` spans the ops of its body
  on the same line and is not counted again;
* the top leaf ops by summed duration, with their result type and opcode;
* idle gaps on the first device, each named by the innermost ``bench.*``
  host span that covers the gap's midpoint (``untraced`` where none does).
"""
from __future__ import annotations

import collections
import gzip
import re

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def load(path: str):
    """Read an ``.xplane.pb`` trace, gzipped where the name ends in
    ``.gz``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _host_spans(pd) -> list[tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, start, dur in _events(line):
                if name.startswith("bench."):
                    spans.append((name, start, start + dur))
    return spans


def _device_ops(pd) -> dict[str, list[tuple[str, float, float]]]:
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [(n, s, s + d) for n, s, d in _events(line)]
    return out


def op_name(text: str) -> str:
    """The op's own HLO name: ``%fusion.12 = f32[..] fusion(..)`` ->
    ``%fusion.12``."""
    return text.split(" = ", 1)[0]


def op_label(text: str) -> str:
    """Name, result type without layout, and opcode of one op."""
    name, _, rest = text.partition(" = ")
    rtype, _, rest = rest.partition(" ")
    return f"{name} {re.sub(r'{[^}]*}', '', rtype)} {rest.split('(', 1)[0]}"


def _leaves(ops):
    """Ops that contain no other op of the same line."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[2]]


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def reduce(pd, n_devices: int | None = None, top: int = 10) -> dict:
    """Busy and window seconds, seconds per op name, top ops and idle gaps
    of one trace, over its first ``n_devices`` devices."""
    spans = _host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = windows[0]
    devices = _device_ops(pd)
    names = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    if not names:
        raise ValueError("the trace holds no device op line")

    busy, op_time, labels = [], collections.Counter(), {}
    for name in names:
        ops = [(n, s, e) for n, s, e in devices[name] if e > lo and s < hi]
        merged = _union([(s, e) for _, s, e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for n, s, e in _leaves(ops):
            key = op_name(n)
            op_time[key] += min(e, hi) - max(s, lo)
            labels.setdefault(key, op_label(n))

    first = _union([(s, e) for _, s, e in devices[names[0]]
                    if e > lo and s < hi], lo, hi)
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = collections.Counter()
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = 0.5 * (g0 + g1)
        cover = [(e - s, n) for n, s, e in inner if s <= mid <= e]
        gaps[min(cover)[1] if cover else "untraced"] += g1 - g0

    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "op_s": {k: v * ns for k, v in op_time.items()},
        "device_ops": [[labels[n], t * ns]
                       for n, t in op_time.most_common(top)],
        "idle_gaps": [[n, t * ns] for n, t in gaps.most_common(top)],
    }
