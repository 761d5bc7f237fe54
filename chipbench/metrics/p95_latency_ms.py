"""The 95th percentile of the same sample as ``p50_latency_ms``: all the
window's requests, not a median of chunks."""
from chipbench import yardstick


def read(record):
    lat = record["latencies_s"]
    return 1e3 * yardstick.percentile(lat, 95) if lat else None
