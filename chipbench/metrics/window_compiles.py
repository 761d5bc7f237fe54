"""Backend compiles, persistent-cache reads included, inside the
window: set-up warms every shape, so this reads 0."""


def read(record):
    return record["window_compiles"]
