"""Unpack's host work per request (``GeometryServer._resolve``: the
finite check, each request's slice and copy, the int16 conversions):
the ``unpack.copy`` span time per request of the traced window."""


def read(record):
    spent = record["spans"].get("unpack.copy")
    if not spent or not record["completed"]:
        return None
    return 1e6 * spent / record["completed"]
