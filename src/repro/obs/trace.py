"""Program spans on the profiler's clock, and the registry of scope names
that label the fleet scan's and the host serve slot's layers in the
device trace.

Spans (:func:`span`) mark host-side phases: fleet argument preparation,
the scan's dispatch, the eager aggregates, streamed segments, the host
tier's slot preparation and dispatch, benchmark phases.  Each span always
enters ``jax.profiler.TraceAnnotation``, so under a profiler trace it
lands on the host plane on the same clock as the device's ops, and costs
one annotation otherwise.

Scopes (:func:`scope`) name the layers of traced code, the fleet scan's
in :data:`SCOPES` and the host serve slot's in :data:`HOST_SCOPES`: each
enters ``jax.named_scope`` with a registered name, which XLA keeps in
every op's metadata (the ``tf_op`` stat of a device op in the trace).
``bench/xplane.py`` reduces a trace to device seconds per scope.  Names
are dotted, never ``/``: the name stack uses ``/`` between levels, and a
scope traced under ``vmap`` reads ``vmap(seeker.x)`` there.

The wall-clock buffer is opt-in: after :func:`enable`, spans also record
``perf_counter`` intervals, optionally flushing async dispatch first
(``flush=``: jax returns futures, so a span around a jitted call would
otherwise time the dispatch, not the work), and
:func:`export_chrome_trace` writes them as Chrome-trace/Perfetto JSON
(``{"traceEvents": [...]}``, ``ph: "X"`` complete events in µs); CI
uploads the file per PR next to the BENCH artifacts.  Disabled, a span
reads no clock and flushes nothing.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import jax

__all__ = ["SCOPES", "HOST_SCOPES", "scope", "enable", "enabled", "clear", "span",
           "instant", "events", "export_chrome_trace"]

SCOPES = {
    "seeker.corr": "the batched signature-bank correlation of the fleet "
                   "(signature_corr_op)",
    "seeker.decide": "predictor, decision ladder, payload bookkeeping and "
                     "supercap update of each node",
    "seeker.edge_dnn": "the on-node quantized DNN (har_apply_quantized, the "
                       "fake_quant kernel)",
    "seeker.aac_coreset": "AAC's k and the per-channel clustering coreset",
    "seeker.importance_coreset": "the importance-sampling coreset",
    "seeker.recover_cluster": "host recovery of a window from its cluster "
                              "coreset",
    "seeker.recover_sampling": "host GAN recovery of a window from its "
                               "sampled coreset",
    "seeker.host_dnn": "the host's full-precision DNN on both recovered "
                       "windows and the logit select",
    "seeker.lanes": "the scan step's lanes: churn freeze, brown-out "
                    "hysteresis, output masks, telemetry",
    "seeker.collectives": "the sharded engine's psums of the fleet "
                          "aggregates and telemetry",
}

# the host tier's serve slot (repro.host.server), registered apart from the
# fleet scan's layers: a fleet trace holds none of these
HOST_SCOPES = {
    "host.ingest": "the host's queue push of a slot's ingest lane and its "
                   "overflow count",
    "host.edf": "deadline expiry, the EDF microbatch pop and the QoS "
                "histograms",
    "host.cache": "payload signatures, the recovery-memo lookup and insert",
    "host.recover": "per-payload recovery keys and the cluster or GAN "
                    "recovery of a microbatch (_entry_windows)",
    "host.dnn": "the host's full-precision DNN on a microbatch",
    "host.ensemble": "the per-node mean-logit and vote ensemble",
}
_REGISTERED = {**SCOPES, **HOST_SCOPES}

_LOCK = threading.Lock()
_ENABLED = False
_EVENTS: list[dict] = []
_T0_NS = time.perf_counter_ns()


def scope(name: str):
    """``jax.named_scope(name)`` for a name registered in :data:`SCOPES` or
    :data:`HOST_SCOPES`; called while tracing, so an unregistered name
    fails at trace time."""
    if name not in _REGISTERED:
        raise ValueError(f"scope {name!r} is not registered in "
                         f"repro.obs.trace.SCOPES or HOST_SCOPES")
    return jax.named_scope(name)


def enable(on: bool = True) -> None:
    """Globally switch the wall-clock span buffer on/off."""
    global _ENABLED
    _ENABLED = on


def enabled() -> bool:
    return _ENABLED


def clear() -> None:
    """Drop all recorded events (the buffer is process-global)."""
    with _LOCK:
        _EVENTS.clear()


def _now_us() -> float:
    return (time.perf_counter_ns() - _T0_NS) / 1e3


def _record(ev: dict) -> None:
    with _LOCK:
        _EVENTS.append(ev)


@contextlib.contextmanager
def span(name: str, cat: str = "repro", args: dict | None = None,
         flush=None):
    """A host span around a block: a profiler ``TraceAnnotation`` always,
    and a wall-clock event in the buffer when tracing is enabled.

    ``flush``: jax arrays (any pytree) or a zero-arg callable returning
    them — with the buffer enabled, ``jax.block_until_ready`` runs on them
    before the end timestamp, so asynchronously-dispatched device work is
    *inside* the span instead of leaking into whatever is timed next.
    Disabled, the body runs untouched: no clock, no flush, no event.
    """
    with jax.profiler.TraceAnnotation(name):
        if not _ENABLED:
            yield
            return
        t0 = _now_us()
        try:
            yield
        finally:
            if flush is not None:
                jax.block_until_ready(flush() if callable(flush) else flush)
            _record({"name": name, "cat": cat, "ph": "X", "ts": t0,
                     "dur": _now_us() - t0, "pid": os.getpid(),
                     "tid": threading.get_ident(),
                     **({"args": args} if args else {})})


def instant(name: str, cat: str = "repro",
            args: dict | None = None) -> None:
    """Record a zero-duration instant event (e.g. a jit retrace — called
    from traced-function bodies, which only run at trace time)."""
    if not _ENABLED:
        return
    _record({"name": name, "cat": cat, "ph": "i", "s": "p",
             "ts": _now_us(), "pid": os.getpid(),
             "tid": threading.get_ident(),
             **({"args": args} if args else {})})


def events() -> list[dict]:
    """Snapshot of the recorded events (copies; safe to mutate)."""
    with _LOCK:
        return [dict(e) for e in _EVENTS]


def export_chrome_trace(path: str) -> int:
    """Write the recorded events as Chrome-trace JSON (Perfetto-loadable);
    returns the number of events written."""
    evs = events()
    with open(path, "w") as f:
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms"}, f)
    return len(evs)
