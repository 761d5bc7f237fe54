"""Percent of the profiled window in which no operation ran on the
device: 100 * (1 - union of device-op intervals / window)."""


def read(record):
    dev = record["device"]
    if not dev or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
