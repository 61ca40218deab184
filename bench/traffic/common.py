"""Pieces the traffic drivers share: a sample of the window's answers, the
check of kept results against the reference, and the work of a set of
queries."""
from __future__ import annotations

import numpy as np

from bench import reference, roofline


class Reservoir:
    """A uniform sample of ``size`` of the items offered, drawn from
    ``rng`` as they come (Algorithm R), so that the sample covers the
    whole window without keeping every answer."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen = size, rng, 0
        self.items: list = []

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            slot = int(self.rng.integers(0, self.seen + 1))
            if slot < self.size:
                self.items[slot] = item
        self.seen += 1


def check_result(ctx, cols: dict, lengths, query_idx, entry_traj,
                 entry_seg, t_enter, t_exit) -> list[dict]:
    """Compare the program's rows for a query array (reference columns
    ``cols``) with the reference, one span of query positions at a time:
    span ``j`` is the ``lengths[j]`` positions after the spans before it
    (a query trajectory's segments).  Every row of the array is judged: a
    row whose query position lies outside every span is judged with the
    first or the last span, where it matches nothing.  Returns the
    numbers of each span."""
    qi = np.asarray(query_idx, np.int64)
    order = np.argsort(qi, kind="stable")
    qs = qi[order]
    cols_out = [np.asarray(a)[order] for a in (entry_traj, entry_seg,
                                               t_enter, t_exit)]
    bounds = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    cut = np.searchsorted(qs, bounds, side="left")
    cut[0], cut[-1] = 0, len(qs)
    out = []
    for j in range(len(lengths)):
        sl = slice(cut[j], cut[j + 1])
        q_ids = np.arange(bounds[j], bounds[j + 1])
        truth = reference.reference(ctx.index, cols, q_ids, ctx.d)
        rows = reference.program_rows(ctx.data, qs[sl], *(
            a[sl] for a in cols_out))
        if ctx.control is not None:
            ctx.control.append(reference.compare(
                truth, reference.reference(ctx.index, cols, q_ids, ctx.d,
                                           dtype=reference.CONTROL_DTYPE)))
        out.append(reference.compare(truth, rows))
    return out


def set_work(ctx, rows: np.ndarray, hits: int) -> tuple[float, float]:
    """(operations, bytes) of evaluating the query segments at dataset
    rows ``rows`` against the database (``bench.roofline``)."""
    qts = ctx.data.cols["ts"][rows]
    qte = ctx.data.cols["te"][rows]
    pairs = reference.overlap_count(ctx.index, qts, qte)
    touched = roofline.entries_touched(ctx.index.cols["ts"],
                                       ctx.index.cols["te"], qts, qte)
    return roofline.work(pairs, touched, len(rows), hits)
