"""The whole step's share of the chip's bf16 peak, in %: the FLOPs that the
window's simulated decisions require (its decision histogram times the
per-path counts of ``bench/flops.py``) over the traced window's seconds and
the chips' peak."""
from bench import flops, peaks


def read(record):
    cfg, tr = record["cfg"], record["trace"]
    work = flops.step_flops(cfg["model"], cfg["k_max"],
                            record["counts"]["histogram"])
    peak = peaks.peaks(record["devices"][0].device_kind)["bf16_flops"]
    if work == 0:
        return None
    return 100.0 * work / tr["window_s"] / (peak * len(record["devices"]))
