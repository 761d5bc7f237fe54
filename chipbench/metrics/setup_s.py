"""Process start until the window opens: imports, traffic, compiles and
compile-cache reads, and the warm pass over every shape."""


def read(record):
    return record["setup_s"]
