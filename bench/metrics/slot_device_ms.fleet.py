"""Device busy time per simulated slot of the whole fleet, in ms: the
trace's busy seconds over the slots the traced window dispatched."""


def read(record):
    slots = record["counts"]["slots"]
    return 1e3 * record["trace"]["busy_s"] / slots if slots else None
