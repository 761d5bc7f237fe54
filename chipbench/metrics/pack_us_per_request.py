"""Bucket packing (``flush``, ``_pack``): the ``bucket.pack`` span time
per request of the traced window."""


def read(record):
    spent = record["spans"].get("bucket.pack")
    if not spent or not record["completed"]:
        return None
    return 1e6 * spent / record["completed"]
