"""fetch_ms.batch: median over the window's query sets of the seconds of
copying result buffers from the device to the host (the program's
``repro.engine.fetch`` span), in ms."""
from bench import spans


def read(run):
    return spans.span_ms(run, "batch", plus=("repro.engine.fetch",))
