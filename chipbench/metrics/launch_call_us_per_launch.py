"""Dispatch's plan call alone (``GeometryServer._call``: the jitted
call that transfers the host operands and enqueues the kernel): the
``launch.call`` span time per launch of the traced window."""


def read(record):
    spent = record["spans"].get("launch.call")
    launches = record["counters"].get("launches")
    if not spent or not launches:
        return None
    return 1e6 * spent / launches
