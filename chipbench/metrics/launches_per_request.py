"""Kernel launches per request served in the window, from the server's
own exact counters."""


def read(record):
    c = record["counters"]
    if not c.get("requests"):
        return None
    return c["launches"] / c["requests"]
