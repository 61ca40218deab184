"""Spans and counters of one query execution.

``span(name, **args)`` marks a step of the query path.  It opens a
``jax.profiler.TraceAnnotation``, so a profiler that is recording sees the
step on the same clock as the device's operations, and it adds the step's
host-clock seconds to the calling thread's :class:`Recorder`, when one is
open.  ``count(name, n)`` adds to a counter of that recorder.

``TrajectoryDB.query`` opens one recorder per call (:func:`recording`);
an executor run outside it opens its own.  The recorder's dicts end up on
the result's ``ExecStats`` as ``span_seconds`` and ``counts``.  A recorder
belongs to one thread, so concurrent queries (the broker's or the
scheduler's pool threads) never mix their numbers; a span on a thread with
no recorder still reaches the profiler.  Every span inside a recorder
carries the recorder's ``qid`` arg, so one request's spans share an
identifier in the trace.

Spans are named ``repro.<layer>.<step>``; the leaves of one execution never
overlap each other, so their totals add up.  With no profiler recording a
span costs about 2 us.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time

from jax.profiler import TraceAnnotation

_local = threading.local()
#: Source of request ids: the only state shared across threads.
_qids = itertools.count(1)


@dataclasses.dataclass
class Recorder:
    """Seconds per span name and counts per counter name of one
    execution."""

    qid: int
    seconds: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)


def current() -> Recorder | None:
    """The calling thread's open recorder, if any."""
    return getattr(_local, "recorder", None)


@contextlib.contextmanager
def recording():
    """Open a recorder on this thread, or join the one already open."""
    rec = current()
    if rec is not None:
        yield rec
        return
    rec = _local.recorder = Recorder(next(_qids))
    try:
        yield rec
    finally:
        _local.recorder = None


class span:
    """``with span(name, **args) as s:`` — a profiler annotation that also
    adds its seconds to the thread's recorder.  ``s.seconds`` holds the
    span's host-clock duration once it has closed."""

    __slots__ = ("name", "args", "seconds", "_rec", "_ann", "_t0")

    def __init__(self, name: str, **args):
        self.name, self.args, self.seconds = name, args, 0.0

    def __enter__(self) -> "span":
        self._rec = current()
        if self._rec is not None:
            self.args["qid"] = self._rec.qid
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._rec is not None:
            sec = self._rec.seconds
            sec[self.name] = sec.get(self.name, 0.0) + self.seconds


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the thread's recorder (nothing when
    none is open)."""
    rec = current()
    if rec is not None:
        rec.counts[name] = rec.counts.get(name, 0) + int(n)


__all__ = ["Recorder", "count", "current", "recording", "span"]
