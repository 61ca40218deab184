"""Query-batch generation algorithms (paper §6, Algorithms 2–4).

All algorithms take the query segments *sorted by non-decreasing t_start*
(the paper's precondition) and partition them into batches of **contiguous**
query segments.  A batch is fully described by its index range
``[q_first, q_last]`` into the sorted query array plus its temporal extent
``[qt0, qt1]`` (``qt0 = ts[q_first]`` by sortedness; ``qt1`` is the running
max of ``te`` over the range, maintained in O(1) across merges).

``numInts(batch) = |batch| × |E_batch|`` where ``|E_batch|`` is the number
of candidate entry segments given by the temporal-bin index (paper §4) —
this is the quantity every algorithm below minimizes increases of.

**Pruning-aware pricing (PR 5).**  With spatial pruning enabled the true
per-batch workload is the *pruned* candidate count, so every merge
decision should price it: pass a :class:`SpatialInteractionCounter` as
``counter=`` and the merge loops evaluate ``numInts`` against the
temporal-bin index's coarse per-bin-MBR grid (conservative, vectorized)
while maintaining each batch's query-MBR union incrementally across
merges.  A merge of two spatially distant batches then has a *positive*
cost even when their temporal extents nest — the algorithms keep
spatially coherent batches, which is what makes the downstream sub-range
split (``repro.core.planner``) effective.  ``counter=None`` (the default)
prices the paper's temporal-only count, bit-for-bit as before.

Algorithms:

* :func:`periodic` — fixed batch size ``s`` (paper §6.1).
* :func:`setsplit_fixed` — Algorithm 2: O(|Q|²) best-merge loop down to a
  target number of batches.
* :func:`setsplit_minmax` — Algorithm 3: best-merge loop with a max-size
  constraint, then a second phase merging undersized batches left/right.
* :func:`setsplit_max` — Algorithm 3 with ``min=1`` (paper §6.2 last line).
* :func:`greedysetsplit_min` / :func:`greedysetsplit_max` — Algorithm 4:
  one pass of "free" merges (merges that add zero interactions), then one
  constraint pass.  The free-merge pass is a left-to-right scan that
  prices look-ahead windows of prefix unions, each under its own prune
  threshold (as a one-row call would), in lanes cut wherever the
  singleton price changes, every lane's window of a round in one stacked
  call; the constraint pass decides on sizes alone and prices its merged
  batches in one call.  O(|Q|) work, as many rounds as the longest lane
  needs, no per-merge array copy.

The SETSPLIT loops are vectorized with numpy (all adjacent-pair merge costs
are evaluated per iteration with ``candidate_range_batch``, the stack under
one shared prune threshold), which keeps the quadratic algorithms usable at
|Q| of a few thousand.  The *semantics* are line-for-line the paper's: each
iteration merges the adjacent pair with the smallest
``numIntsMerged − numIntsUnmerged``.

Public entry point: algorithm selection lives in
``repro.api.ExecutionPolicy(batching=..., batch_params=...)``; the facade
calls into :data:`ALGORITHMS` and owns the sortedness precondition.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

from repro.core import spans
from repro.core.index import TemporalBinIndex
from repro.core.segments import SegmentArray


@dataclasses.dataclass(frozen=True)
class QueryBatch:
    """A contiguous run of sorted query segments plus its candidate range."""

    q_first: int           # inclusive index into the sorted query array
    q_last: int            # inclusive
    qt0: float             # temporal extent start (= ts[q_first])
    qt1: float             # temporal extent end (= max te over the range)
    cand_first: int        # inclusive candidate entry index (0, -1 if empty)
    cand_last: int         # inclusive
    num_ints: int          # |batch| × num_candidates

    @property
    def size(self) -> int:
        return self.q_last - self.q_first + 1

    @property
    def num_candidates(self) -> int:
        return max(self.cand_last - self.cand_first + 1, 0)


@dataclasses.dataclass
class BatchPlan:
    """Output of a batching algorithm plus provenance for EXPERIMENTS.md."""

    algorithm: str
    params: dict
    batches: list[QueryBatch]
    plan_seconds: float    # time spent computing the plan (paper §7.4 charges this)

    @property
    def total_interactions(self) -> int:
        return int(sum(b.num_ints for b in self.batches))

    @property
    def num_batches(self) -> int:
        return len(self.batches)

    def sizes(self) -> np.ndarray:
        return np.array([b.size for b in self.batches], dtype=np.int64)


class SpatialInteractionCounter:
    """Prices ``numInts`` with spatial pruning folded in.

    Bound to one (index, sorted query set, threshold): per-batch candidate
    counts come from the index's coarse per-bin-MBR estimate
    (:meth:`~repro.core.index.TemporalBinIndex.
    estimate_pruned_candidates_batch`) evaluated against each batch's
    query-MBR union.  ``level="box"`` prices against the K-box-per-bin
    hierarchy (``pruning="hierarchical"``); ``max_subranges`` folds the
    planner's sub-range cap into the price (the cap can re-admit a
    fragmented extent's gap segments, and the coarse grid charges a
    conservative surcharge for that — see the estimate's docstring), so
    the priced count tracks the capped dispatched workload instead of the
    uncapped ideal.
    """

    def __init__(self, index: TemporalBinIndex, queries: SegmentArray,
                 d: float, *, level: str = "bin",
                 max_subranges: int | None = None):
        self.index = index
        self.d = float(d)
        self.level = level
        self.max_subranges = max_subranges
        self.qlo, self.qhi = queries.mbrs()      # (nq, 3) float64

    def counts(self, qt0, qt1, lo, hi) -> np.ndarray:
        """Pruned candidate counts for batches with extents (qt0, qt1) and
        query-MBR unions (lo, hi) — all stacked arrays."""
        return self.index.estimate_pruned_candidates_batch(
            qt0, qt1, lo, hi, self.d, level=self.level,
            max_subranges=self.max_subranges)

    def row_counts(self, qt0, qt1, lo, hi) -> np.ndarray:
        """:meth:`counts` with each row under its own prune threshold, as
        a one-row call would price it."""
        return self.index.estimate_pruned_candidates_rows(
            qt0, qt1, lo, hi, self.d, level=self.level,
            max_subranges=self.max_subranges)


# ----------------------------------------------------------------------
# internal representation used by the merge loops: parallel arrays over
# the current batch list B.  Batches are contiguous and ordered, so batch
# k is [starts[k], starts[k] + sizes[k] - 1].
# ----------------------------------------------------------------------
class _BatchState:
    def __init__(self, index: TemporalBinIndex, queries: SegmentArray,
                 counter: SpatialInteractionCounter | None = None):
        if not queries.is_sorted():
            raise ValueError("queries must be sorted by t_start (paper §4)")
        nq = len(queries)
        if nq == 0:
            raise ValueError("empty query set")
        self.index = index
        self.counter = counter
        self.starts = np.arange(nq, dtype=np.int64)
        self.sizes = np.ones(nq, dtype=np.int64)
        self.qt0 = queries.ts.astype(np.float64).copy()
        self.qt1 = queries.te.astype(np.float64).copy()
        if counter is not None:
            # Per-batch query-MBR unions, maintained across merges.
            self.mlo = counter.qlo.copy()
            self.mhi = counter.qhi.copy()
            self.num_ints = self.sizes * counter.counts(
                self.qt0, self.qt1, self.mlo, self.mhi)
        else:
            self.mlo = self.mhi = None
            self.num_ints = self.sizes * index.num_candidates_batch(
                self.qt0, self.qt1)

    def __len__(self) -> int:
        return len(self.starts)

    def merge_costs(self) -> np.ndarray:
        """numIntsMerged − numIntsUnmerged for every adjacent pair (vectorized)."""
        m_qt0 = self.qt0[:-1]                                 # sorted ⇒ min is left's
        m_qt1 = np.maximum(self.qt1[:-1], self.qt1[1:])
        m_size = self.sizes[:-1] + self.sizes[1:]
        if self.counter is not None:
            m_lo = np.minimum(self.mlo[:-1], self.mlo[1:])
            m_hi = np.maximum(self.mhi[:-1], self.mhi[1:])
            merged = m_size * self.counter.counts(m_qt0, m_qt1, m_lo, m_hi)
        else:
            merged = m_size * self.index.num_candidates_batch(m_qt0, m_qt1)
        return merged - (self.num_ints[:-1] + self.num_ints[1:])

    def merged_sizes(self) -> np.ndarray:
        return self.sizes[:-1] + self.sizes[1:]

    def merged_ints(self, i: int) -> int:
        """numInts of the would-be merge of batches i and i+1 (the scalar
        the GREEDYSETSPLIT free-merge test and the MINMAX fix-up use)."""
        qt0 = self.qt0[i]
        qt1 = max(self.qt1[i], self.qt1[i + 1])
        size = int(self.sizes[i] + self.sizes[i + 1])
        if self.counter is not None:
            lo = np.minimum(self.mlo[i], self.mlo[i + 1])
            hi = np.maximum(self.mhi[i], self.mhi[i + 1])
            return size * int(self.counter.counts(
                np.array([qt0]), np.array([qt1]), lo[None], hi[None])[0])
        return size * self.index.num_candidates(qt0, qt1)

    def merge_at(self, i: int) -> None:
        """Merge batches i and i+1 in place (paper's merge + removeElementAt)."""
        self.qt1[i] = max(self.qt1[i], self.qt1[i + 1])
        self.sizes[i] += self.sizes[i + 1]
        if self.counter is not None:
            self.mlo[i] = np.minimum(self.mlo[i], self.mlo[i + 1])
            self.mhi[i] = np.maximum(self.mhi[i], self.mhi[i + 1])
            self.num_ints[i] = self.sizes[i] * int(self.counter.counts(
                self.qt0[i:i + 1], self.qt1[i:i + 1],
                self.mlo[i][None], self.mhi[i][None])[0])
        else:
            self.num_ints[i] = self.sizes[i] * self.index.num_candidates(
                self.qt0[i], self.qt1[i])
        for name in ("starts", "sizes", "qt0", "qt1", "num_ints"):
            arr = getattr(self, name)
            setattr(self, name, np.delete(arr, i + 1))
        if self.counter is not None:
            self.mlo = np.delete(self.mlo, i + 1, axis=0)
            self.mhi = np.delete(self.mhi, i + 1, axis=0)

    def to_batches(self) -> list[QueryBatch]:
        first, last = self.index.candidate_range_batch(self.qt0, self.qt1)
        out = []
        for k in range(len(self.starts)):
            out.append(QueryBatch(
                q_first=int(self.starts[k]),
                q_last=int(self.starts[k] + self.sizes[k] - 1),
                qt0=float(self.qt0[k]), qt1=float(self.qt1[k]),
                cand_first=int(first[k]), cand_last=int(last[k]),
                num_ints=int(self.num_ints[k]),
            ))
        return out


def _finish(name: str, params: dict, state_or_batches, t_start: float) -> BatchPlan:
    batches = (state_or_batches.to_batches()
               if isinstance(state_or_batches, _BatchState) else state_or_batches)
    return BatchPlan(algorithm=name, params=params, batches=batches,
                     plan_seconds=time.perf_counter() - t_start)


# ----------------------------------------------------------------------
# PERIODIC (paper §6.1)
# ----------------------------------------------------------------------
def periodic(index: TemporalBinIndex, queries: SegmentArray, s: int, *,
             counter: SpatialInteractionCounter | None = None) -> BatchPlan:
    """Fixed-size batches of ``s`` consecutive sorted query segments.

    PERIODIC makes no merge decisions, so ``counter`` is accepted for
    interface uniformity only — the pruned workload is priced downstream
    by the planner's sub-range refinement.
    """
    del counter
    t_begin = time.perf_counter()
    if s <= 0:
        raise ValueError("batch size must be positive")
    nq = len(queries)
    starts = np.arange(0, nq, s, dtype=np.int64)
    ends = np.minimum(starts + s, nq) - 1
    qt0 = queries.ts[starts].astype(np.float64)
    # max te within each chunk, via a prefix-max free approach: reduceat.
    qt1 = np.maximum.reduceat(queries.te.astype(np.float64), starts)
    first, last = index.candidate_range_batch(qt0, qt1)
    sizes = ends - starts + 1
    ints = sizes * np.maximum(last - first + 1, 0)
    batches = [QueryBatch(int(a), int(b), float(t0), float(t1), int(f), int(l), int(i))
               for a, b, t0, t1, f, l, i
               in zip(starts, ends, qt0, qt1, first, last, ints)]
    return _finish("periodic", {"s": s}, batches, t_begin)


# ----------------------------------------------------------------------
# SETSPLIT (paper §6.2, Algorithms 2 & 3)
# ----------------------------------------------------------------------
def setsplit_fixed(index: TemporalBinIndex, queries: SegmentArray,
                   num_batches: int, *,
                   counter: SpatialInteractionCounter | None = None
                   ) -> BatchPlan:
    """Algorithm 2: merge the cheapest adjacent pair until |B| = numBatches."""
    t_begin = time.perf_counter()
    st = _BatchState(index, queries, counter)
    num_batches = max(1, num_batches)
    while len(st) > num_batches:
        costs = st.merge_costs()
        st.merge_at(int(np.argmin(costs)))
    return _finish("setsplit-fixed", {"num_batches": num_batches}, st, t_begin)


def setsplit_minmax(index: TemporalBinIndex, queries: SegmentArray,
                    min_size: int, max_size: int, *,
                    counter: SpatialInteractionCounter | None = None
                    ) -> BatchPlan:
    """Algorithm 3: constrained best-merge loop + undersize fix-up passes."""
    t_begin = time.perf_counter()
    if min_size > max_size:
        raise ValueError("min_size > max_size")
    st = _BatchState(index, queries, counter)
    # Phase 1 (lines 3–21): best merge among pairs whose merged size <= max.
    while True:
        if len(st) == 1:
            break
        costs = st.merge_costs().astype(np.float64)
        costs[st.merged_sizes() > max_size] = np.inf   # line 6: skip oversize merges
        i = int(np.argmin(costs))
        if not np.isfinite(costs[i]):                  # line 16: minDiff = +inf
            break
        st.merge_at(i)
    # Phase 2 (lines 22–40): merge undersized batches with cheaper neighbour.
    while True:
        small = np.nonzero(st.sizes < min_size)[0]
        if small.size == 0 or len(st) == 1:
            break
        i = int(small[0])
        left = st.merged_ints(i - 1) if i > 0 else np.inf
        right = st.merged_ints(i) if i < len(st) - 1 else np.inf
        if left < right:
            st.merge_at(i - 1)
        else:
            st.merge_at(i)
    return _finish("setsplit-minmax", {"min": min_size, "max": max_size}, st, t_begin)


def setsplit_max(index: TemporalBinIndex, queries: SegmentArray,
                 max_size: int, *,
                 counter: SpatialInteractionCounter | None = None
                 ) -> BatchPlan:
    """SETSPLIT-MINMAX with min = 1 (paper §6.2, final paragraph)."""
    plan = setsplit_minmax(index, queries, 1, max_size, counter=counter)
    plan.algorithm = "setsplit-max"
    plan.params = {"max": max_size}
    return plan


# ----------------------------------------------------------------------
# GREEDYSETSPLIT (paper §6.3, Algorithm 4)
# ----------------------------------------------------------------------
#: Most rows one GREEDYSETSPLIT pricing call stacks.  The coarse-grid
#: estimate materialises ``(rows, COARSE_GRID_BINS[, K], 3)`` float64
#: temporaries, so a round's stack of prefix unions is priced in chunks of
#: this many rows (about 6 MB a temporary at ``level="bin"``).
SCAN_CHUNK_ROWS = 2048


def _greedy(index: TemporalBinIndex, queries: SegmentArray, bound: int,
            variant: str,
            counter: SpatialInteractionCounter | None = None) -> BatchPlan:
    """Algorithm 4 as a scan of many lanes at once and one pass over sizes.

    Phase 1 (lines 4–11) merges ``B[i]`` and ``B[i+1]`` iff the merge is
    free: ``numInts(merge) == numInts(B[i]) + numInts(B[i+1])``.  The right
    neighbour of the batch being grown is always an untouched singleton,
    so the pass is a scan: from the open batch ``A = [a, j]`` it prices the
    prefix unions ``A ∪ {j+1 … k}`` of a look-ahead window of ``L``
    segments (running max of ``te``, running min/max of the query MBRs),
    and the first ``k`` whose price is not the previous prefix's plus
    singleton ``k``'s closes ``A`` at ``k−1``.  A window that passes whole
    extends ``A`` and doubles ``L``; a new batch starts with ``L`` twice
    the closed batch's size.

    **Lanes.**  With a price ``c`` that is monotone in the union (the
    temporal range only grows, the MBR gap only shrinks, the threshold
    only grows with the coordinate scale — every pricing with
    ``max_subranges=None``), a merge is free only if
    ``(s+1)·c(A∪k) = s·c(A) + c(k)`` with ``c(A∪k) ≥ max(c(A), c(k))``,
    that is only if all three are equal: a phase-1 batch never crosses a
    change in the singleton price.  The sorted queries are therefore cut
    into lanes at every change of the singleton price, each lane runs the
    scan above over its own rows, and every round prices the windows of
    all unfinished lanes in one stacked call (in chunks of
    :data:`SCAN_CHUNK_ROWS` rows).  A lane's windows reach one row past
    its end, which the sequential scan would price to close the lane's
    last batch.  Where that row still merges free — a price that is not
    monotone (the ``max_subranges`` surcharge), or singletons priced
    under a wider shared threshold than the union — the lane absorbs the
    next lane and scans on into it, and everything that lane found is
    dropped; its first row was no batch boundary.  So the plan never rests
    on monotonicity, which only decides how wide the scan goes.

    Each prefix union is priced under its own prune threshold
    (:meth:`SpatialInteractionCounter.row_counts`), as the merge-by-merge
    loop's one-row calls priced it, whatever it is stacked with; the
    singletons are priced in one stacked call, as that loop's initial
    state was.  Phase 2 (lines 13–20) decides on sizes alone, so it runs
    over the phase-1 sizes and prices only the batches it merged.  The
    plan is the merge-by-merge loop's, batch for batch.  The spans
    counters ``plan_price_calls`` and ``plan_price_rows`` count the pricing
    calls (chunks included) and the rows they priced; ``plan_scan_lanes``,
    ``plan_scan_rounds`` and ``plan_scan_restarts`` the lanes formed, the
    rounds run and the lanes absorbed.
    """
    t_begin = time.perf_counter()
    if not queries.is_sorted():
        raise ValueError("queries must be sorted by t_start (paper §4)")
    nq = len(queries)
    if nq == 0:
        raise ValueError("empty query set")
    ts = queries.ts.astype(np.float64)
    te = queries.te.astype(np.float64)
    calls, rows = 1, nq                   # the singletons' stacked call

    def price(qt0, qt1, lo, hi, size) -> np.ndarray:
        """numInts of batches, each under its own prune threshold."""
        nonlocal calls, rows
        rows += len(qt1)
        out = np.empty(len(qt1), np.int64)
        for c in range(0, len(qt1), SCAN_CHUNK_ROWS):
            s = slice(c, c + SCAN_CHUNK_ROWS)
            calls += 1
            out[s] = (index.num_candidates_batch(qt0[s], qt1[s])
                      if counter is None
                      else counter.row_counts(qt0[s], qt1[s], lo[s], hi[s]))
        return size * out

    if counter is not None:
        qlo, qhi = counter.qlo, counter.qhi
        single = counter.counts(ts, te, qlo, qhi)
    else:                                 # MBRs carried along, never read
        qlo = qhi = np.zeros((nq, 3))
        single = index.num_candidates_batch(ts, te)

    # A prefix union's te, upper MBR corner and negated lower corner are
    # running maxima, taken over integer ranks into their sorted values:
    # each round lifts every lane's ranks clear of the lanes before it, so
    # one flat accumulate never carries from one window into the next.
    cols = np.c_[te, qhi, -qlo]
    vals, rank = np.unique(cols, return_inverse=True)
    rank = rank.reshape(nq, 7)

    # Phase 1: lane i holds the open batch [a[i], j[i]], whose price is
    # a_ints[i] and running maxima run[i]; its windows reach row lim[i],
    # one past its end (-1 once absorbed), and nxt[i] is the lane after
    # it.  Closed batches are kept with their lane, so that an absorbed
    # lane's can be dropped.
    a = np.r_[0, np.flatnonzero(np.diff(single)) + 1]
    end = np.r_[a[1:], nq] - 1
    lim = np.minimum(end + 1, nq - 1)
    j = a.copy()
    a_ints, run = single[a].astype(np.int64), cols[a]
    width = np.full(len(a), 2)
    nxt = np.arange(1, len(a) + 1)
    closed: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    rounds = restarts = 0
    live = np.flatnonzero(j < lim)
    while live.size:
        rounds += 1
        jl, al = j[live], a[live]
        n = np.minimum(jl + width[live], lim[live]) - jl   # at least 1
        stop = np.cumsum(n)               # flat end of each window
        head = stop - n                   # and its first flat row
        flat = np.arange(stop[-1])
        k = flat + np.repeat(jl + 1 - head, n)
        lane = np.repeat(live, n)
        a0 = a[lane]
        lift = (lane * len(vals))[:, None]
        acc = np.maximum(
            vals[np.maximum.accumulate(rank[k] + lift, axis=0) - lift],
            run[lane])
        merged = price(ts[a0], acc[:, 0], -acc[:, 4:], acc[:, 1:4],
                       k - a0 + 1)
        prev = np.empty_like(merged)
        prev[1:] = merged[:-1]
        prev[head] = a_ints[live]
        # Each window's first merge that is not free, or its end.
        pos = np.minimum(np.minimum.reduceat(
            np.where(merged == prev + single[k], stop[-1], flat), head), stop)
        close = pos < stop                # row k[pos] starts a new batch
        jn = jl + pos - head              # the open batch's last row
        row = jn + close                  # the next j, a new batch's start
        closed.append((live[close], al[close], prev[pos[close]]))
        a[live] = np.where(close, row, al)
        j[live] = row
        width[live] = 2 * np.where(close, row - al, width[live])
        a_ints[live] = np.where(close, single[row], merged[pos - 1])
        run[live] = np.where(close[:, None], cols[row], acc[pos - 1])
        # The batch merged free into the next lane's first row: take that
        # lane over, last lane first so that chains of crossings compose.
        for i in live[jn > end[live]][::-1]:
            restarts += 1
            end[i], lim[i] = end[nxt[i]], lim[nxt[i]]
            lim[nxt[i]] = -1
            nxt[i] = nxt[nxt[i]]
        live = np.flatnonzero(j < lim)
    # The open last batch, and every closed one of a lane not absorbed.
    tail = np.flatnonzero((end == nq - 1) & (lim >= 0))
    closed.append((tail, a[tail], a_ints[tail]))
    lanes, starts, ints = (np.concatenate(x) for x in zip(*closed))
    keep = lim[lanes] >= 0
    order = np.argsort(starts[keep])
    starts, ints = starts[keep][order], ints[keep][order]
    spans.count("plan_scan_lanes", len(a))
    spans.count("plan_scan_rounds", rounds)
    spans.count("plan_scan_restarts", restarts)

    # Phase 2: merge batch i into its successors while it is under the
    # bound ("min"), or has not yet exceeded it ("max": the bound is soft,
    # the merge that crosses it is still made, as the literal
    # transformation of line 14 dictates).
    sizes = np.diff(np.r_[starts, nq]).tolist()
    groups = []                           # first phase-1 batch of each
    i = 0
    while i < len(sizes):
        groups.append(i)
        size = sizes[i]
        while i < len(sizes) - 1 and (size < bound if variant == "min"
                                      else size <= bound):
            i += 1
            size += sizes[i]
        i += 1
    first_q = np.asarray(starts, np.int64)[groups]
    last_q = np.r_[first_q[1:], nq] - 1
    qt0 = ts[first_q]
    qt1 = np.maximum.reduceat(te, first_q)
    num_ints = np.asarray(ints, np.int64)[groups]
    joined = np.diff(np.r_[groups, len(sizes)]) > 1
    if joined.any():
        num_ints[joined] = price(
            qt0[joined], qt1[joined],
            np.minimum.reduceat(qlo, first_q, axis=0)[joined],
            np.maximum.reduceat(qhi, first_q, axis=0)[joined],
            (last_q - first_q + 1)[joined])
    spans.count("plan_price_calls", calls)
    spans.count("plan_price_rows", rows)
    cand_first, cand_last = index.candidate_range_batch(qt0, qt1)
    batches = [QueryBatch(int(q0), int(q1), float(t0), float(t1), int(f),
                          int(l), int(n))
               for q0, q1, t0, t1, f, l, n in zip(
                   first_q, last_q, qt0, qt1, cand_first, cand_last,
                   num_ints)]
    return _finish(f"greedysetsplit-{variant}", {"bound": bound}, batches,
                   t_begin)


def greedysetsplit_min(index: TemporalBinIndex, queries: SegmentArray,
                       bound: int, *,
                       counter: SpatialInteractionCounter | None = None
                       ) -> BatchPlan:
    """Algorithm 4: free merges, then merge any batch smaller than ``bound``."""
    return _greedy(index, queries, bound, "min", counter)


def greedysetsplit_max(index: TemporalBinIndex, queries: SegmentArray,
                       bound: int, *,
                       counter: SpatialInteractionCounter | None = None
                       ) -> BatchPlan:
    """Algorithm 4 MAX variant (paper §6.3 prose)."""
    return _greedy(index, queries, bound, "max", counter)


ALGORITHMS: dict[str, Callable] = {
    "periodic": periodic,
    "setsplit-fixed": setsplit_fixed,
    "setsplit-max": setsplit_max,
    "setsplit-minmax": setsplit_minmax,
    "greedysetsplit-min": greedysetsplit_min,
    "greedysetsplit-max": greedysetsplit_max,
}
