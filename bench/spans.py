"""Per-layer numbers the program records inside ``db.query``, for the
readers in ``bench/metrics``.

Each call of ``TrajectoryDB.query`` leaves on its result's ``ExecStats``
the host-clock seconds of its spans (``span_seconds``, by span name:
``repro.<layer>.<step>``) and its counters (``counts``).  A traffic driver
copies the two into each execution's record, under ``spans`` and
``counts`` (``bench/traffic/closed_sets.py`` does), and a reader here
returns the median over every execution of the window that carries them,
or ``None`` where the traffic is of another kind or the program records
no spans (an older program).
"""
from __future__ import annotations

import collections

import numpy as np

from bench import readers

#: What an execution's record copies of the program's ``ExecStats``,
#: under the same names, for a reader's ``fn``.
Stats = collections.namedtuple("Stats", "span_seconds counts")


def recorded(run, kind: str) -> list | None:
    """The spans and counters of the window's executions that carry the
    program's spans, or None."""
    execs = readers.execs(run, kind)
    if execs is None:
        return None
    stats = [Stats(e["spans"], e["counts"]) for e in execs
             if e.get("spans") and "counts" in e]
    return stats or None


def median(run, kind: str, fn) -> float | None:
    """Median of ``fn(stats)`` over the window's executions."""
    stats = recorded(run, kind)
    if stats is None:
        return None
    return float(np.median([fn(st) for st in stats]))


def span_ms(run, kind: str, plus=(), minus=()) -> float | None:
    """Median over the window's executions of the seconds of the spans
    ``plus`` less those of ``minus``, in ms."""
    def ms(st):
        sec = st.span_seconds
        return 1e3 * (sum(sec.get(n, 0.0) for n in plus)
                      - sum(sec.get(n, 0.0) for n in minus))
    return median(run, kind, ms)


def ratio(run, kind: str, num: str, den: str) -> float | None:
    """Median over the window's executions of counter ``num`` over
    counter ``den`` (None where no execution counted any ``den``)."""
    stats = recorded(run, kind)
    if stats is None:
        return None
    vals = [st.counts.get(num, 0) / st.counts[den] for st in stats
            if st.counts.get(den)]
    return float(np.median(vals)) if vals else None
