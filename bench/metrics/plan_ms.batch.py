"""plan_ms.batch: median over the window's query sets of the planner's
own seconds (``ExecStats.plan_seconds``), in ms."""
from bench import readers


def read(run):
    sets = readers.execs(run, "batch")
    return readers.median_ms(s["plan_s"] for s in sets) if sets else None
