"""Read a profiler trace (``.xplane.pb``) with the standard library alone,
and reduce it to device seconds per named scope of the program.

``jax.profiler.ProfileData`` gives each event its own stats but not its
metadata's, and the metadata's ``tf_op`` stat is where a device op keeps
the JAX name stack it was traced under (``jit(run)/while/body/...``).  This
module decodes the XSpace protobuf's wire format itself, only the fields
it needs (``planes``, ``lines``, ``events``, ``event_metadata``,
``stat_metadata``), and gives the same planes, lines and events as
``ProfileData`` (``name``, ``start_ns``, ``duration_ns``, computed alike),
each event with its metadata's ``tf_op`` as well.  ``trace_reduce.reduce``
reads what ``load`` returns unchanged.

:func:`reduce` then gives, over the ``bench.window`` host span:

* ``scope_s``: device seconds of leaf ops per registered scope
  (:data:`repro.obs.trace.SCOPES`), the innermost scope named in the op's
  ``tf_op`` (a scope traced under ``vmap`` reads ``vmap(seeker.x)``, so
  names are matched inside path components), mean over the devices, and
  ``unscoped`` where the op names none;
* ``idle_gaps``: idle time of the first device, each gap named by the
  innermost ``bench.*`` or ``fleet.*`` host span that covers its midpoint
  (``untraced`` where none does).

    python3 bench/xplane.py <trace.xplane.pb[.gz]> [n_devices]

prints both as JSON.
"""
from __future__ import annotations

import collections
import gzip
import json
import re
import sys
from typing import NamedTuple

if __package__ in (None, ""):
    from pathlib import Path
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import trace_reduce  # noqa: E402

SPAN_PREFIXES = ("bench.", "fleet.")
UNSCOPED = "unscoped"


class Event(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    tf_op: str


class Line(NamedTuple):
    name: str
    events: list


class Plane(NamedTuple):
    name: str
    lines: list


class Space(NamedTuple):
    planes: list


# --- protobuf wire format ---------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) slice for a length-delimited field; fixed-width fields
    are skipped (none of those this reader needs is fixed-width)."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif kind == 1:
            i += 8
        elif kind == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_value(buf: bytes, span):
    """The value slice of one map<int64, Message> entry."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return None


def _stat_metadata(buf: bytes, spans) -> dict[int, str]:
    out = {}
    for span in spans:
        value = _map_value(buf, span)
        if value is None:
            continue
        sid, name = 0, ""
        for f, v in _fields(buf, *value):
            if f == 1:
                sid = v
            elif f == 2:
                name = _text(buf, v)
        out[sid] = name
    return out


def _event_metadata(buf: bytes, spans, stats) -> dict[int, tuple]:
    """id -> (name, tf_op) of each event metadata; a string stat holds its
    text itself or refers to the stat metadata whose name it is."""
    tf_op_id = next((k for k, v in stats.items() if v == "tf_op"), None)
    out = {}
    for span in spans:
        value = _map_value(buf, span)
        if value is None:
            continue
        mid, name, tf_op = 0, "", ""
        for f, v in _fields(buf, *value):
            if f == 1:
                mid = v
            elif f == 2:
                name = _text(buf, v)
            elif f == 5 and tf_op_id is not None:
                stat_id, text = None, None
                for g, w in _fields(buf, *v):
                    if g == 1:
                        stat_id = w
                    elif g == 5:
                        text = _text(buf, w)
                    elif g == 7:
                        text = stats.get(w)
                if stat_id == tf_op_id and text is not None:
                    tf_op = text
        out[mid] = (name, tf_op)
    return out


def _line(buf: bytes, span, metadata) -> Line:
    name, ts_ns, events = "", 0, []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            ts_ns = _signed(v)
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = offset_ps = duration_ps = 0
        for f, v in _fields(buf, *ev):
            if f == 1:
                mid = v
            elif f == 2:
                offset_ps = _signed(v)
            elif f == 3:
                duration_ps = _signed(v)
        ev_name, tf_op = metadata.get(mid, ("", ""))
        # whole nanoseconds, as ProfileData gives them
        out.append(Event(ev_name, float(ts_ns + offset_ps // 1000),
                         float(duration_ps // 1000), tf_op))
    return Line(name, out)


def _plane(buf: bytes, span) -> Plane:
    name, lines, ev_meta, stat_meta = "", [], [], []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.append(v)
        elif f == 5:
            stat_meta.append(v)
    metadata = _event_metadata(buf, ev_meta, _stat_metadata(buf, stat_meta))
    return Plane(name, [_line(buf, s, metadata) for s in lines])


def parse(buf: bytes) -> Space:
    """The planes of one serialized XSpace."""
    return Space([_plane(buf, v) for f, v in _fields(buf, 0, len(buf))
                  if f == 1])


def load(path: str) -> Space:
    """Read an ``.xplane.pb`` trace, gzipped where the name ends in
    ``.gz``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return parse(f.read())


# --- reduction --------------------------------------------------------------

def scope_pattern(scopes) -> re.Pattern:
    """Matches a registered scope name inside a ``tf_op`` path; names are
    dotted, so a match may not run on into further name characters."""
    alts = "|".join(re.escape(s) for s in sorted(scopes, key=len,
                                                 reverse=True))
    return re.compile(rf"(?<![\w.])(?:{alts})(?![\w.])")


def innermost_scope(tf_op: str, pattern: re.Pattern) -> str:
    found = pattern.findall(tf_op)
    return found[-1] if found else UNSCOPED


def _spans(space) -> list[tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in space.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIXES)]


def reduce(space, scopes, n_devices: int | None = None,
           top: int = 10) -> dict:
    """``scope_s`` and ``idle_gaps`` of one trace (see the module's
    docstring), over its first ``n_devices`` devices."""
    spans = _spans(space)
    windows = [(s, e) for n, s, e in spans if n == trace_reduce.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {trace_reduce.WINDOW_SPAN!r} "
                         "host span")
    lo, hi = windows[0]
    devices = {}
    for plane in space.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices[plane.name] = [
                        (e.tf_op, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
    names = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    if not names:
        raise ValueError("the trace holds no device op line")

    pattern = scope_pattern(scopes)
    scope_ns = collections.Counter({s: 0.0 for s in scopes})
    scope_ns[UNSCOPED] = 0.0
    for name in names:
        # a zero-length op (a buffer allocation at the start of a fusion)
        # holds no time and must not make the op it starts in a container
        timed = [op for op in devices[name] if op[2] > op[1]]
        for tf_op, s, e in trace_reduce._leaves(timed):
            scope_ns[innermost_scope(tf_op, pattern)] += min(e, hi) - max(s,
                                                                          lo)

    busy = trace_reduce._union([(s, e) for _, s, e in devices[names[0]]],
                               lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    inner = [(n, s, e) for n, s, e in spans if n != trace_reduce.WINDOW_SPAN]
    gaps = collections.Counter()
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = 0.5 * (g0 + g1)
        cover = [(e - s, n) for n, s, e in inner if s <= mid <= e]
        gaps[min(cover)[1] if cover else "untraced"] += g1 - g0

    ns = 1e-9
    return {"scope_s": {k: v * ns / len(names) for k, v in scope_ns.items()},
            "idle_gaps": [[n, t * ns] for n, t in gaps.most_common(top)]}


def main(argv) -> None:
    from repro.obs.trace import SCOPES
    n_devices = int(argv[1]) if len(argv) > 1 else None
    print(json.dumps(reduce(load(argv[0]), SCOPES, n_devices), indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
