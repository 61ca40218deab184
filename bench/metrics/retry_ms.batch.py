"""retry_ms.batch: median over the window's query sets of the seconds of
overflow re-dispatches, their waits and count reads (the program's
``repro.exec.retry`` span), in ms."""
from bench import spans


def read(run):
    return spans.span_ms(run, "batch", plus=("repro.exec.retry",))
