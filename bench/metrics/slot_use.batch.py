"""slot_use.batch: median over the window's query sets of the share of the
result-buffer slots copied back that held a row (the program's
``result_rows`` over ``result_slots``), a fraction."""
from bench import spans


def read(run):
    return spans.ratio(run, "batch", "result_rows", "result_slots")
