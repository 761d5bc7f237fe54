"""Requests whose results came back in the window, over all the
window's seconds."""
from chipbench import yardstick


def read(record):
    return yardstick.rate(record["completed"], record["window_s"])
