"""canonical_ms.batch: median over the window's query sets of the seconds
of the facade's mapping of the rows to the caller's query order and its
canonical sort (the program's ``repro.facade.canonical`` span), in ms."""
from bench import spans


def read(run):
    return spans.span_ms(run, "batch", plus=("repro.facade.canonical",))
