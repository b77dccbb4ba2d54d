"""Record the small profiler trace the trace-reduction test reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Runs the fleet cell ``har_wearables.fleet`` small (16 nodes, 2-slot
segments) for a tenth of a second under the profiler, writes the trace
gzipped to the given path, and prints the trace's planes, lines and most
frequent op names, so that the names the metric readers look for can be
checked by hand.
"""
import collections
import gzip
import shutil
import sys
import time
from pathlib import Path

_HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(_HERE.parents[1]), str(_HERE.parents[1] / "src")]

from bench import common, trace_reduce  # noqa: E402
from bench.drivers import fleet as fl  # noqa: E402


def main(out: str) -> None:
    common.enable_cache()
    import jax
    devices = jax.devices()[:1]
    cfg = common.load_json(common.BENCH / "configs" / "har_wearables.json")
    traffic = common.load_json(common.BENCH / "traffic" / "fleet.json")
    cfg["n_nodes"] = 16
    traffic.update(segment_slots=2, horizon_slots=4, sample_nodes=4)
    limits = common.load_json(common.BENCH / "limits"
                              / "har_wearables.fleet.json")
    rec = fl.run({}, cfg, traffic, limits, 7, 0.1, True, devices,
                 time.perf_counter())
    path = sorted(Path(rec["trace_dir"]).rglob("*.xplane.pb"))[-1]
    with open(path, "rb") as src, gzip.open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    pd = trace_reduce.load(str(path))
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines)
        for ln in plane.lines:
            events = list(ln.events)
            names = collections.Counter(e.name for e in events)
            print("   ", ln.name, names.most_common(25))
            for e in events[:3]:
                print("      stats", e.name, dict(e.stats))
    red = trace_reduce.reduce(pd, n_devices=1)
    print({k: v for k, v in red.items() if k != "op_s"})


if __name__ == "__main__":
    main(sys.argv[1])
