"""Readings for a cell's limits: the program against the reference, and the
precision control (the reference with matrix products one step below the
configuration's precision) against the reference, on many seeds in one
process.

    python3 bench/calibrate.py --workload <cell> --seeds 101 102 ... \
        [--seconds 20] [--fault <name>] [--out readings.jsonl]

Each seed builds the cell as a run does, runs a window of ``--seconds`` at
the cell's own load, and compares the same sampled nodes over the same
segments a run would.  One JSON line per seed and side.  A planted fault
(``--fault``, see ``bench/faults.py``) breaks the timed path underneath.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(_HERE.parent), str(_HERE.parent / "src")]

from bench import common, compare, faults  # noqa: E402
from bench.drivers import fleet as fl  # noqa: E402

# the nearest matrix precision below each one a configuration may state
CONTROL = {"highest": "high"}


def readings(workload: str, seed: int, seconds: float, devices,
             fault: str | None = None) -> list[dict]:
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == workload)
    cfg = common.load_json(common.BENCH / "configs" / f"{cell['config']}.json")
    traffic = common.load_json(common.BENCH / "traffic"
                               / f"{cell['traffic']}.json")
    fleet = fl.Fleet(cfg, traffic, seed, devices[:cell["chips"]])
    with faults.planted(fault):
        carry = fleet.boot()
        p, carry = fleet.segment(0, carry)
        common.block((p, carry))
        picks, ready, _ = fl.window(fleet, seconds, fleet.boot())
    prog = fl.program_traces(picks)
    ref = fl.reference_traces(fleet, len(ready))
    side = fault or "program"
    out = [{"seed": seed, "side": side, "segments": len(ready),
            **compare.compare(prog, ref)}]
    if fault is None:
        low = CONTROL[fleet.precision]
        ctl = fl.reference_traces(fleet, len(ready), low)
        out.append({"seed": seed, "side": f"control_{low}",
                    "segments": len(ready), **compare.compare(ctl, ref)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", default=None, choices=faults.FAULTS)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    common.enable_cache()
    import jax
    devices = jax.devices()
    sink = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        t0 = time.perf_counter()
        for row in readings(args.workload, seed, args.seconds, devices,
                            args.fault):
            row["workload"] = args.workload
            row["wall_s"] = time.perf_counter() - t0
            line = json.dumps(row)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
