"""Fleet-wide observability: metrics registry, spans, scopes, compile guard.

One telemetry substrate for every engine and the host tier (ISSUE 8):

* :mod:`repro.obs.registry` — declared-once counter/gauge/histogram lanes on
  a jit-friendly pytree; exact int accounting, psum-able, resume-exact;
* :mod:`repro.obs.trace` — host spans and registered layer scopes on the
  profiler's clock, plus an opt-in wall-clock buffer (``block_until_ready``
  flush idiom, Chrome-trace/Perfetto JSON export);
* :mod:`repro.obs.compile_guard` — (re)trace events as a tracked,
  budget-guarded metric (the generalized ``serve_trace_count`` probe).
"""
from . import trace  # noqa: F401
from .compile_guard import (  # noqa: F401
    CompileBudgetError, compile_count, compile_counts, compile_event,
    compile_guard, compile_key_counts, reset_compile_counts,
)
from .registry import (  # noqa: F401
    Lane, MetricsSpec, categorical_counts, counter, counter_add,
    counter_value, gauge, gauge_set, hist_observe, histogram, int_pair_sum,
    int_pair_total, lane_edges, metrics_init, metrics_merge, metrics_psum,
    metrics_summary, percentile_from_hist, spec_union,
)
