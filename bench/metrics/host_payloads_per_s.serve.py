"""Payloads the host served per second of the window: the ``served``
counter of the host's state after the last slot ready inside the window,
over the time from the window's start to that slot."""


def read(record):
    secs = record["window_s_host"]
    return record["counts"]["served_in_window"] / secs if secs else None
