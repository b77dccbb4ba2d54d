"""Share of its roofline that the fake-quantization kernel reaches, in %:
the least time of one slot's calls (each quantized tensor read and written
once; memory-bound) times the slots traced, over the summed device time of
the kernel's ops."""
from bench import flops, peaks
from bench.metrics_kernel import kernel_share

NAMES = ("fake_quant", "_quant_kernel")


def read(record):
    cfg = record["cfg"]
    cost = flops.fake_quant_cost(cfg["model"], cfg["n_nodes"])
    peak = peaks.peaks(record["devices"][0].device_kind)
    return kernel_share(record, NAMES, cost, peak)
