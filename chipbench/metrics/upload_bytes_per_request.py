"""Host-to-device bytes staged per request served in the window: the
server's ``upload_bytes`` counter (each launch's packed points and
folds) over its ``requests``.  On a resident mesh only the folds go up:
22 float32 words, 88 bytes, for a 3-D projective request."""


def read(record):
    c = record["counters"]
    if c.get("upload_bytes") is None or not c.get("requests"):
        return None
    return c["upload_bytes"] / c["requests"]
