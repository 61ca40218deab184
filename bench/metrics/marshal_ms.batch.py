"""marshal_ms.batch: median over the window's query sets of the host
remainder of ``db.query``: its wall time less the planner's, dispatch and
sync seconds (``ExecStats``).  That is copy-back, marshalling, retries and
the facade's sorting, in ms."""
from bench import readers


def read(run):
    sets = readers.execs(run, "batch")
    if not sets:
        return None
    return readers.median_ms(s["wall_s"] - s["plan_s"] - s["dispatch_s"]
                             - s["sync_s"] for s in sets)
