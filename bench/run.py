"""The chip benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the driver the mix names in
``bench/drivers/<driver>.py``, each per-layer metric's reader in
``bench/metrics/<metric>.py`` and the cell's limits in
``bench/limits/<cell>.json``.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and the numbers
compared with their limits under ``checks``).  Off a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(_HERE.parent), str(_HERE.parent / "src")]

from bench import common, trace_reduce  # noqa: E402
from bench.common import BENCH, ROOT, log  # noqa: E402


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: dict, kind: str) -> list[dict]:
    """The metrics of ``kind`` ('end_to_end' or 'per_layer') this cell
    reports: those that list it, or that list no cells (a per-layer metric
    then goes with every cell that reports the metric it moves)."""
    mine = lambda m: cell["name"] in m.get("workloads", [cell["name"]])
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (mine(m) if "workloads" in m else m["moves"] in moved)]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices, sizes: dict | None = None) -> dict:
    """Run one cell on ``devices`` and return the result object.
    ``sizes`` ({"config": {...}, "traffic": {...}}) overrides entries of the
    cell's files; the CPU tests use it to run a cell small."""
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, workload)
    cfg = common.load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = common.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    cfg.update((sizes or {}).get("config", {}))
    traffic.update((sizes or {}).get("traffic", {}))
    limits = common.load_json(BENCH / "limits" / f"{workload}.json")
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    record = driver.run(cell, cfg, traffic, limits, seed, seconds, trace,
                        devices, T_START)

    device = common.device_info(devices)
    device["memory_peak_bytes"] = int(record["end_to_end"]["peak_hbm_gb"]
                                      * 1e9)
    out = {"correct": bool(record["correct"]),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"])}
    if not trace:
        wanted = cell_metrics(bench, cell, "end_to_end")
        out["metrics"] = {m["name"]: {"value": record["end_to_end"][m["name"]],
                                      "unit": m["unit"]} for m in wanted}
    else:
        tdir = record.pop("trace_dir")
        paths = sorted(Path(tdir).rglob("*.xplane.pb"))
        pd = trace_reduce.load(str(paths[-1]))
        record["trace"] = trace_reduce.reduce(pd, n_devices=len(devices))
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        out["metrics"] = {}
        for m in cell_metrics(bench, cell, "per_layer"):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(record)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    out["device"] = device
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in record["checks"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    cache = common.enable_cache()
    import jax
    devices = jax.devices()
    log(f"device: {common.device_info(devices)}; compile cache {cache}")
    if devices[0].platform != "tpu":
        log(f"bench: JAX found no TPU (platform {devices[0].platform!r})")
        return 2
    if len(devices) < cell["chips"]:
        log(f"bench: {args.workload} needs {cell['chips']} chips, found "
            f"{len(devices)}")
        return 2
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices[:cell["chips"]])
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
