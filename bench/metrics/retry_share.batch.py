"""retry_share.batch: median over the window's query sets of the share of
first dispatches whose result buffers overflowed, so that the batch was
dispatched again (the program's ``retried_dispatches`` over
``dispatches``), a fraction.  The planner sizes the first capacities."""
from bench import spans


def read(run):
    return spans.ratio(run, "batch", "retried_dispatches", "dispatches")
