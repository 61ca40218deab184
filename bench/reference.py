"""Plain reference of the distance-threshold query, and the comparison
that decides a run's ``correct``.

The query (paper §3): for every entry segment ``e`` and query segment ``q``
whose closed time extents overlap in ``[lo, hi]``, report the sub-interval
of ``[lo, hi]`` in which their positions lie within distance ``d``, when it
is not empty.  Both move linearly over their extents, so their separation
``r(t) = r(lo) + dv (t - lo)`` is linear in ``t`` and ``|r(t)|^2 <= d^2`` is
a quadratic inequality in ``tau = t - lo``.

This module computes that straightforwardly in float64 from the float32
values the program is given.  It imports nothing of the program and uses
nothing the program made: pairs are enumerated from the benchmark's own
copy of the data, and rows are matched by (query position, entry
trajectory, entry segment), which the benchmark assigned.

The same code run in bfloat16 is the control: the reference put in the
program's place one precision below the float32 the configuration states.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

from bench.datagen import COLUMNS, Dataset

#: The control's precision: the one below the configurations' float32.
CONTROL_DTYPE = ml_dtypes.bfloat16

#: Pairs evaluated per block: small enough that a block's temporaries stay
#: in the CPU's cache (about twice as fast as blocks of 2M pairs).
BLOCK_PAIRS = 1 << 16

#: A matched row counts in ``t_gap`` only where the pair comes within
#: ``(1 - TANGENT_MARGIN) * d``: a pair that dips inside ``d`` by less is
#: near-tangent, and its interval endpoints move by the square root of any
#: rounding, in every precision.  Such rows still count in the depths.
TANGENT_MARGIN = 0.01


class EntryIndex:
    """The database segments sorted by start time, for pair enumeration."""

    def __init__(self, data: Dataset):
        order = np.argsort(data.cols["ts"], kind="stable")
        self.rows = order.astype(np.int64)
        self.cols = {c: data.cols[c][order] for c in COLUMNS}
        self.ts = self.cols["ts"].astype(np.float64)
        self.te_sorted = np.sort(self.cols["te"].astype(np.float64))
        self.max_dur = float(np.max(self.cols["te"].astype(np.float64)
                                    - self.ts)) if len(order) else 0.0
        self.num_rows = len(order)

    def candidates(self, qts: np.ndarray, qte: np.ndarray):
        """Per query segment, the sorted-entry range that can overlap it:
        every overlapping entry starts in [qts - max_dur, qte]."""
        lo = np.searchsorted(self.ts, qts - self.max_dur, side="left")
        hi = np.searchsorted(self.ts, qte, side="right")
        return lo, hi


def overlap_count(index: EntryIndex, qts: np.ndarray, qte: np.ndarray
                  ) -> int:
    """Exact number of (entry, query) pairs whose closed time extents
    overlap: entries starting no later than the query ends, less those
    that ended before it started (a subset of the former)."""
    started = np.searchsorted(index.ts, np.asarray(qte, np.float64),
                              side="right")
    ended = np.searchsorted(index.te_sorted, np.asarray(qts, np.float64),
                            side="left")
    return int(np.sum(started - ended))


def _pairs(index: EntryIndex, qts, qte, q_ids):
    """Yield blocks ``(q_local, e_sorted)`` of temporally overlapping pairs
    for the query segments ``q_ids`` (positions into qts/qte)."""
    lo, hi = index.candidates(qts[q_ids], qte[q_ids])
    n = hi - lo
    start = 0
    while start < len(q_ids):
        stop = start + 1
        acc = n[start]
        while stop < len(q_ids) and acc + n[stop] <= BLOCK_PAIRS:
            acc += n[stop]
            stop += 1
        sel = np.arange(start, stop)
        cnt = n[sel]
        q_rep = np.repeat(q_ids[sel], cnt)
        base = np.repeat(lo[sel] - np.concatenate([[0], np.cumsum(cnt)[:-1]]),
                         cnt)
        e = base + np.arange(int(cnt.sum()))
        keep = (index.cols["te"][e].astype(np.float64)
                >= qts[q_rep]) & (index.ts[e] <= qte[q_rep])
        yield q_rep[keep], e[keep]
        start = stop


def solve(e: dict, q: dict, d: float, dtype):
    """Hit mask, interval and (float64 only) minimum separation of paired
    segments, every operation in ``dtype``.

    ``e`` and ``q`` map each column to equal-length float32 arrays (one
    row per pair).  Velocities are (end - start) / duration, zero for a
    zero-length extent.  Returns ``(hit, t_enter, t_exit, min_dist)``;
    ``min_dist`` is the separation's minimum over the overlap.
    """
    f = {k: np.asarray(v).astype(dtype) for k, v in e.items()}
    g = {k: np.asarray(v).astype(dtype) for k, v in q.items()}
    zero = dtype(0)
    lo = np.maximum(f["ts"], g["ts"])
    hi = np.minimum(f["te"], g["te"])
    length = hi - lo
    rel, dv = [], []
    for a, b in (("xs", "xe"), ("ys", "ye"), ("zs", "ze")):
        vel = []
        for s in (f, g):
            dur = s["te"] - s["ts"]
            safe = np.where(dur > zero, dur, dtype(1))
            vel.append(np.where(dur > zero, (s[b] - s[a]) / safe, zero))
        # Positions at lo, and the velocity difference.
        pe = f[a] + vel[0] * (lo - f["ts"])
        pq = g[a] + vel[1] * (lo - g["ts"])
        rel.append(pe - pq)
        dv.append(vel[0] - vel[1])
    qa = dv[0] * dv[0] + dv[1] * dv[1] + dv[2] * dv[2]
    qb = dtype(2) * (rel[0] * dv[0] + rel[1] * dv[1] + rel[2] * dv[2])
    dd = dtype(d)
    qc = rel[0] * rel[0] + rel[1] * rel[1] + rel[2] * rel[2] - dd * dd
    quad = qa > zero
    disc = qb * qb - dtype(4) * qa * qc
    sq = np.sqrt(np.where(disc > zero, disc, zero))
    safe_a = np.where(quad, qa, dtype(1))
    # Roots of a tau^2 + b tau + c in the stable form: q / a and c / q.
    qq = -(qb + np.where(qb >= zero, sq, -sq)) / dtype(2)
    safe_q = np.where(qq != zero, qq, dtype(1))
    r1 = np.where(qq != zero, qq / safe_a, zero)
    r2 = np.where(qq != zero, qc / safe_q, zero)
    rlo = np.minimum(r1, r2)
    rhi = np.maximum(r1, r2)
    inside = np.where(quad, disc >= zero, qc <= zero)
    tau_in = np.where(quad, np.maximum(rlo, zero), zero)
    tau_out = np.where(quad, np.minimum(rhi, length), length)
    hit = (length >= zero) & inside & (tau_in <= tau_out)
    t_enter = lo + tau_in
    t_exit = lo + tau_out
    min_dist = None
    if dtype is np.float64:
        tau = np.where(quad, np.clip(-qb / (dtype(2) * safe_a), zero,
                                     np.maximum(length, zero)), zero)
        sep = [rel[k] + dv[k] * tau for k in range(3)]
        min_dist = np.sqrt(sep[0] ** 2 + sep[1] ** 2 + sep[2] ** 2)
    return hit, t_enter, t_exit, min_dist


@dataclasses.dataclass
class Rows:
    """Result rows in the benchmark's terms: ``key`` is query position
    times the database size plus the entry's dataset row."""

    key: np.ndarray       # int64
    t_enter: np.ndarray   # float64
    t_exit: np.ndarray    # float64


@dataclasses.dataclass
class Truth:
    """Every temporally overlapping pair of the checked query segments,
    with the float64 answer: sorted by ``key``."""

    key: np.ndarray
    hit: np.ndarray
    t_enter: np.ndarray
    t_exit: np.ndarray
    min_dist: np.ndarray
    d: float


def _gather(cols: dict, idx) -> dict:
    return {c: cols[c][idx] for c in COLUMNS}


def reference(index: EntryIndex, queries: dict, q_ids: np.ndarray,
              d: float, dtype=np.float64):
    """The answer for query segments ``q_ids`` (positions into the
    ``queries`` columns) in ``dtype``: a :class:`Truth` for float64, the
    control's :class:`Rows` for a lower precision."""
    qts = queries["ts"].astype(np.float64)
    qte = queries["te"].astype(np.float64)
    exact = dtype is np.float64
    keys, hits, t_ins, t_outs, mins = [], [], [], [], []
    for q_loc, e_sorted in _pairs(index, qts, qte,
                                  np.asarray(q_ids, np.int64)):
        hit, t_in, t_out, m = solve(_gather(index.cols, e_sorted),
                                    _gather(queries, q_loc), d, dtype)
        key = q_loc * np.int64(index.num_rows) + index.rows[e_sorted]
        if not exact:
            key, t_in, t_out = key[hit], t_in[hit], t_out[hit]
        keys.append(key)
        hits.append(hit)
        t_ins.append(t_in.astype(np.float64))
        t_outs.append(t_out.astype(np.float64))
        mins.append(m)
    if not keys:
        keys, hits, t_ins, t_outs, mins = ([np.zeros(0, np.int64)],
                                           [np.zeros(0, bool)],
                                           [np.zeros(0)], [np.zeros(0)],
                                           [np.zeros(0)])
    key = np.concatenate(keys)
    if not exact:
        return Rows(key, np.concatenate(t_ins), np.concatenate(t_outs))
    order = np.argsort(key, kind="stable")
    return Truth(key[order], np.concatenate(hits)[order],
                 np.concatenate(t_ins)[order], np.concatenate(t_outs)[order],
                 np.concatenate(mins)[order], float(d))


def program_rows(data: Dataset, q_pos: np.ndarray, entry_traj: np.ndarray,
                 entry_seg: np.ndarray, t_enter, t_exit) -> Rows:
    """The program's rows as :class:`Rows`.  An entry id outside the
    dataset maps to key -1, which matches nothing."""
    traj = np.asarray(entry_traj, np.int64)
    seg = np.asarray(entry_seg, np.int64)
    ok = (traj >= 0) & (traj < data.num_traj)
    tc = np.where(ok, traj, 0)
    ok &= (seg >= 0) & (seg < data.offsets[tc + 1] - data.offsets[tc])
    row = data.offsets[tc] + seg
    key = np.where(ok, np.asarray(q_pos, np.int64) * np.int64(len(data))
                   + row, -1)
    return Rows(key, np.asarray(t_enter, np.float64),
                np.asarray(t_exit, np.float64))


#: The numbers compared (see :func:`compare`).
NUMBERS = ("miss_depth", "extra_depth", "t_gap")


def compare(truth: Truth, rows: Rows) -> dict:
    """The numbers that decide ``correct``, for rows that cover exactly
    the query segments ``truth`` was computed for.

    * ``miss_depth`` — over reference hits the rows lack, the largest
      ``(d - min_dist) / d``: how far inside ``d`` a missed pair came.
    * ``extra_depth`` — over rows the reference does not count as hits,
      the largest ``(min_dist - d) / d``; a row for a pair whose extents
      do not overlap, with an unknown entry, or repeating a pair already
      reported, reads 1e9.
    * ``t_gap`` — over matched rows that are not near-tangent, the widest
      difference of ``t_enter`` or ``t_exit`` from the reference's, in
      the data's time units (a non-finite value reads 1e9).
    """
    d = truth.d
    big = 1e9
    key = rows.key
    uniq, first = np.unique(key, return_index=True)
    pos = np.searchsorted(truth.key, uniq)
    pos_c = np.minimum(pos, max(truth.key.size - 1, 0))
    known = (truth.key.size > 0) & (truth.key[pos_c] == uniq) & (uniq >= 0)
    matched_hit = np.zeros(truth.key.size, bool)
    idx_known = pos_c[known]
    matched_hit[idx_known] = True
    missed = truth.hit & ~matched_hit
    miss_depth = float(np.max((d - truth.min_dist[missed]) / d,
                              initial=0.0))
    extra = np.zeros(uniq.size)
    extra[~known] = big
    kh = truth.hit[idx_known]
    extra[known] = np.where(kh, 0.0, (truth.min_dist[idx_known] - d) / d)
    extra_depth = float(np.max(extra, initial=0.0))
    if key.size > uniq.size:
        extra_depth = big
    sel = first[known][kh]
    tix = idx_known[kh]
    well = truth.min_dist[tix] <= (1.0 - TANGENT_MARGIN) * d
    gap = np.maximum(np.abs(rows.t_enter[sel] - truth.t_enter[tix]),
                     np.abs(rows.t_exit[sel] - truth.t_exit[tix]))[well]
    gap = np.where(np.isfinite(gap), gap, big)
    t_gap = float(np.max(gap, initial=0.0))
    return {"miss_depth": miss_depth, "extra_depth": extra_depth,
            "t_gap": t_gap}


def worst(readings: list[dict]) -> dict:
    """The largest of each number over several comparisons."""
    return {k: max((r[k] for r in readings), default=0.0) for k in NUMBERS}
