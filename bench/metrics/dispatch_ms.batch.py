"""dispatch_ms.batch: median over the window's query sets of the seconds
of their first dispatches (the program's ``repro.engine.dispatch`` span:
host slicing, the jit call's enqueue and the upload), in ms."""
from bench import spans


def read(run):
    return spans.span_ms(run, "batch", plus=("repro.engine.dispatch",))
