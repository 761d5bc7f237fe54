"""Dispatch (``_stage``, the plan call): the ``flush.dispatch`` span
time per launch of the traced window."""


def read(record):
    spent = record["spans"].get("flush.dispatch")
    launches = record["counters"].get("launches")
    if not spent or not launches:
        return None
    return 1e6 * spent / launches
