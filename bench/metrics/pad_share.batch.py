"""pad_share.batch: median over the window's query sets of the share of
the rows handed to the kernels' jit call that were padding (the program's
``pad_rows`` over ``dispatched_rows``, retries included), a fraction: what
bucketing the dispatch shapes costs in uploaded rows."""
from bench import spans


def read(run):
    return spans.ratio(run, "batch", "pad_rows", "dispatched_rows")
