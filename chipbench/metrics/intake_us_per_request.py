"""Intake (``GeometryServer.validate``: copy, checks, fold): the
``request.validate`` span time per request of the traced window."""


def read(record):
    spent = record["spans"].get("request.validate")
    if not spent or not record["completed"]:
        return None
    return 1e6 * spent / record["completed"]
