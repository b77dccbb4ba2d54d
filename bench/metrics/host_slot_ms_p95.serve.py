"""95th percentile of the host-clock time of one host slot, in ms: the
time between the slot's results being ready and the previous slot's (the
first from the window's start), over the slots ready inside the window."""
import numpy as np


def read(record):
    slot_s = record["slot_s"]
    return 1e3 * float(np.percentile(slot_s, 95)) if slot_s else None
