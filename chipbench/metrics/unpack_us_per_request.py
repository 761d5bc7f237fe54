"""Unpack (``_unpack``, with the wait for each launch's result): the
``flush.unpack`` span time per request of the traced window."""


def read(record):
    spent = record["spans"].get("flush.unpack")
    if not spent or not record["completed"]:
        return None
    return 1e6 * spent / record["completed"]
