"""Unpack's wait (``block_until_ready`` on a launch's outputs, the host
blocked on the device): the ``unpack.wait`` span time per launch of the
traced window."""


def read(record):
    spent = record["spans"].get("unpack.wait")
    launches = record["counters"].get("launches")
    if not spent or not launches:
        return None
    return 1e6 * spent / launches
