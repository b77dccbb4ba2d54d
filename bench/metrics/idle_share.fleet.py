"""Share of the traced window in which no op ran on the device (mean over
the cell's chips): 1 - busy / window, in %."""


def read(record):
    tr = record["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
