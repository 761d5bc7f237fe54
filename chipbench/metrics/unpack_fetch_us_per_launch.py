"""Unpack's device-to-host transfer of a launch's ready outputs: the
``unpack.fetch`` span time per launch of the traced window."""


def read(record):
    spent = record["spans"].get("unpack.fetch")
    launches = record["counters"].get("launches")
    if not spent or not launches:
        return None
    return 1e6 * spent / launches
