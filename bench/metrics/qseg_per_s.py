"""qseg_per_s: query segments of every query set that completed in the
window, over the window's seconds (batch cells)."""
from bench import readers


def read(run):
    sets = readers.execs(run, "batch")
    if not sets:
        return None
    return sum(s["qsegs"] for s in sets) / run.record["elapsed_s"]
