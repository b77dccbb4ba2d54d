"""Roofline share of one kernel from the trace reduction."""
from bench import flops


def kernel_seconds(record, names) -> float:
    return sum(t for op, t in record["trace"]["op_s"].items()
               if any(n in op for n in names))


def kernel_share(record, names, cost_per_slot: dict, peak: dict):
    """Least time of the window's calls over the kernel's device time, in
    %; None where the trace holds none of the kernel's ops."""
    spent = kernel_seconds(record, names)
    if spent <= 0.0:
        return None
    least, _ = flops.least_seconds(cost_per_slot, peak)
    return 100.0 * least * record["counts"]["slots"] / spent
