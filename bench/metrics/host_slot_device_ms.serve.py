"""Device busy time per host slot, in ms: the trace's busy seconds over
the host slots the traced window dispatched."""


def read(record):
    slots = record["counts"]["slots"]
    return 1e3 * record["trace"]["busy_s"] / slots if slots else None
