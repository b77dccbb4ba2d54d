"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only NAME] [--quick] [--out F]
[--emit-metrics F] [--trace-out F]``

Prints ``name,us_per_call,derived`` CSV (derived = the module's headline
metric per row) followed by human-readable tables, and writes the raw rows
to experiments/bench_results.json (or ``--out``).

``--quick`` runs tiny shapes (the CI bench-smoke job: crash detection + a
perf-trajectory artifact, not a measurement) on every module whose ``run``
accepts a ``quick`` kwarg.  Any benchmark that raises marks the whole run
failed: the harness still executes the remaining modules, then exits
non-zero so CI surfaces the breakage instead of swallowing it.

``--trace-out F`` wraps every module in a :func:`repro.obs.trace.span` and
writes the run as Chrome-trace/Perfetto JSON (load it at ui.perfetto.dev);
compile events fired by the engines appear as instant markers on the same
timeline.  ``--emit-metrics F`` dumps a JSON sidecar with the per-module
wall times and the process-wide compile counts from
:mod:`repro.obs.compile_guard` — the "did this PR add a retrace?" artifact.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

MODULES = [
    "table1_compression",
    "table2_strategies",
    "fig2_quantization",
    "fig6_clusters",
    "fig10_commercial",
    "fig11_system",
    "fig12_endtoend",
    "fig13_bearing",
    "comm_volume",
    "fleet_scale",
    "host_throughput",
]


def _derived(row: dict) -> str:
    for k in ("acc", "acc_scheduled", "total_uj", "windows_per_s",
              "payloads_per_s", "reduction_x", "completed_frac",
              "wire_bytes_per_dev", "volume_frac"):
        if k in row:
            return f"{k}={row[k]:.4f}" if isinstance(row[k], float) \
                else f"{k}={row[k]}"
    return ""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single module (substring match)")
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes for CI smoke (modules whose run() "
                         "takes a quick kwarg)")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="result JSON path (default: "
                         "experiments/bench_results.json)")
    ap.add_argument("--emit-metrics", default=None, metavar="FILE",
                    help="also write a JSON metrics dump (per-module wall "
                         "times + repro.obs compile counts)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="record a Chrome-trace/Perfetto JSON of the run "
                         "(one span per benchmark module)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.obs import compile_counts, trace as obs_trace
    if args.trace_out:
        obs_trace.enable()

    import importlib
    all_rows: list[dict] = []
    failed: list[str] = []
    wall: dict[str, float] = {}
    print("name,us_per_call,derived")
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        t0 = time.time()
        try:
            with obs_trace.span(f"bench.{modname}", cat="bench",
                                args={"quick": args.quick}):
                mod = importlib.import_module(f"benchmarks.{modname}")
                if args.quick and \
                        "quick" in inspect.signature(mod.run).parameters:
                    rows = mod.run(quick=True)
                else:
                    rows = mod.run()
        except Exception as e:  # keep the harness alive per-module ...
            print(f"{modname}/ERROR,0,{type(e).__name__}:{e}")
            failed.append(modname)          # ... but fail the run at the end
            continue
        for row in rows:
            print(f"{row['name']},{row.get('us_per_call', 0.0):.1f},"
                  f"{_derived(row)}")
        all_rows.extend(rows)
        wall[modname] = round(time.time() - t0, 1)
        all_rows.append({"name": f"_meta/{modname}",
                         "wall_s": wall[modname]})

    out = args.out or os.path.join(os.path.dirname(__file__), "..",
                                   "experiments", "bench_results.json")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(all_rows, f, indent=1)
    print(f"# wrote {os.path.normpath(out)}")

    if args.trace_out:
        obs_trace.export_chrome_trace(args.trace_out)
        print(f"# wrote {args.trace_out} (load at ui.perfetto.dev)")
    if args.emit_metrics:
        dump = {"wall_s": wall, "compile_counts": compile_counts(),
                "quick": args.quick, "failed": failed}
        os.makedirs(os.path.dirname(os.path.abspath(args.emit_metrics)),
                    exist_ok=True)
        with open(args.emit_metrics, "w") as f:
            json.dump(dump, f, indent=1)
        print(f"# wrote {args.emit_metrics}")
    if failed:
        sys.exit(f"benchmarks raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
