"""setup_s: seconds from the start of data generation to the end of
the warm-up: data, database and index build, compiles or compile-cache
loads, and the warm-up traffic."""


def read(run):
    return run.setup_s
