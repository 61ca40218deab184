"""Per-layer numbers the program records inside ``db.query``, for the
readers in ``bench/metrics``.

Each call of ``TrajectoryDB.query`` leaves on its result's ``ExecStats``
the host-clock seconds of its spans (``span_seconds``, by span name:
``repro.<layer>.<step>``) and its counters (``counts``).  The closed-loop
traffic (``bench/traffic/closed_sets.py``) keeps the results of the
executions it checks, a sample drawn from the seed over the whole window
(``check_sets`` of them).  A reader here
returns the median over that sample, or ``None`` where the traffic is of
another kind or the program records no spans (an older program).
"""
from __future__ import annotations

import numpy as np

from bench import readers


def recorded(run, kind: str) -> list | None:
    """The ``ExecStats`` of the window's kept executions that carry the
    program's spans, or None."""
    if readers.execs(run, kind) is None:
        return None
    stats = [getattr(res, "stats", None)
             for _, res in run.record.get("kept", ())]
    stats = [st for st in stats
             if getattr(st, "span_seconds", None) and hasattr(st, "counts")]
    return stats or None


def median(run, kind: str, fn) -> float | None:
    """Median of ``fn(stats)`` over the kept executions."""
    stats = recorded(run, kind)
    if stats is None:
        return None
    return float(np.median([fn(st) for st in stats]))


def span_ms(run, kind: str, plus=(), minus=()) -> float | None:
    """Median over the kept executions of the seconds of the spans
    ``plus`` less those of ``minus``, in ms."""
    def ms(st):
        sec = st.span_seconds
        return 1e3 * (sum(sec.get(n, 0.0) for n in plus)
                      - sum(sec.get(n, 0.0) for n in minus))
    return median(run, kind, ms)


def ratio(run, kind: str, num: str, den: str) -> float | None:
    """Median over the kept executions of counter ``num`` over counter
    ``den`` (None where no execution counted any ``den``)."""
    stats = recorded(run, kind)
    if stats is None:
        return None
    vals = [st.counts.get(num, 0) / st.counts[den] for st in stats
            if st.counts.get(den)]
    return float(np.median(vals)) if vals else None
