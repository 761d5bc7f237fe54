"""Median latency over every request completed in the window, from its
own submit call to the return of the flush that holds it."""
from chipbench import yardstick


def read(record):
    lat = record["latencies_s"]
    return 1e3 * yardstick.percentile(lat, 50) if lat else None
