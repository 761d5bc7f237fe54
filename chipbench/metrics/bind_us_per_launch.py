"""Binding a resident bucket (``GeometryServer._bind``: its folds
stacked, the handle's buffer taken as the points): the ``bucket.bind``
span time per launch of the traced window."""


def read(record):
    spent = record["spans"].get("bucket.bind")
    launches = record["counters"].get("launches")
    if not spent or not launches:
        return None
    return 1e6 * spent / launches
