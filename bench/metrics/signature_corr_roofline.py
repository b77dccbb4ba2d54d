"""Share of its roofline that the signature-correlation kernel reaches, in
%: the least time of every call in the traced window (one call a slot, its
bytes over the HBM bandwidth; it is memory-bound) over the summed device
time of the kernel's ops."""
from bench import flops, peaks
from bench.metrics_kernel import kernel_share

NAMES = ("signature_corr", "_corr_kernel")


def read(record):
    cfg = record["cfg"]
    cost = flops.signature_corr_cost(cfg["model"], cfg["n_nodes"])
    peak = peaks.peaks(record["devices"][0].device_kind)
    return kernel_share(record, NAMES, cost, peak)
