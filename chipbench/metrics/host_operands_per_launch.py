"""Host arrays each launch in the window staged, on average: the
server's ``host_operands`` counter over its ``launches``.  Each host
array a plan call takes is its own host-to-device copy.  A launch on
the array path stages its fold row and its packed points, 2; a
resident launch its fold row alone, 1.  A program without the counter
reads nothing."""


def read(record):
    c = record["counters"]
    if c.get("host_operands") is None or not c.get("launches"):
        return None
    return c["host_operands"] / c["launches"]
