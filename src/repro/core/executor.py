"""Execution layer: dispatcher protocol + the sync / pipelined executors.

The counterpart of ``repro.core.planner``: a :class:`~repro.core.planner.
QueryPlan` says *what* to run; this module runs it.  The PR 2 two-phase
pipelined dispatch (phase A: async-dispatch every batch with no host reads;
phase B: one ``block_until_ready``, exact counts, re-dispatch only
overflowed batches, sync once more) is generalized into an executor that
drives any :class:`BatchDispatcher` — the seam that lets the single-device
engine (``repro.core.engine``) and the sharded mesh backend
(``repro.core.distributed.ShardedEngine``) share the ≤ 2-host-syncs-per-
query-set property instead of each reimplementing the loop.

A dispatcher answers four questions, all device-strategy-specific:

* ``dispatch(batch, capacity)`` — queue the batch's device computation
  asynchronously (slicing, padding, sharding — whatever the strategy
  needs) and return a :class:`Dispatch` handle whose ``out`` is blockable.
* ``count(dp)`` — the exact global hit count, read *after* a sync (for the
  sharded dispatcher this is the ``psum``-reduced total).
* ``retry_capacity(dp)`` — ``None`` if the dispatch's buffers held every
  hit, else the (bucketed, ≥ doubled) capacity a re-dispatch needs.  The
  kernels always report exact counts, so one retry always converges.
* ``marshal(dp, count)`` — host-side assembly of the device buffers into a
  ``ResultSet`` part.

Two executors drive a dispatcher over a plan:

* :class:`SyncExecutor` — the classic per-batch loop (dispatch → sync →
  maybe retry → marshal).  One host sync per invocation; per-batch
  timings (host wall until the outputs are ready) are observable, which
  the §8 perf-model fits need.
* :class:`PipelinedExecutor` — the two-phase dispatch, *per dispatch
  group*: group k+1 is dispatched before group k is synced and marshalled,
  so host-side result assembly of group k overlaps device compute of group
  k+1.  With the default single-group plan this is exactly PR 2's O(1)-sync
  executor (``ExecStats.num_syncs ≤ 2``); with G groups it is ≤ 2·G syncs
  and marshalling never leaves the device idle between groups.

``ResultSet`` / ``BatchStats`` / ``ExecStats`` moved here from
``repro.core.engine`` (which re-exports them — import paths are stable).

Both executors time their steps as spans (``repro.core.spans``), and
``ExecStats``/``BatchStats`` take their seconds from them:
``repro.exec.group`` (a group or group phase), ``repro.engine.dispatch``
(a first dispatch), ``repro.exec.sync`` (the first wait and its count
reads), ``repro.exec.retry`` (overflow re-dispatches), ``repro.exec.marshal``
and ``repro.exec.concat``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable, Protocol, runtime_checkable

import jax
import numpy as np

from repro.core import spans
from repro.core.batching import QueryBatch
from repro.core.errors import CapacityError
from repro.core.planner import QueryPlan


# ----------------------------------------------------------------------
# Dispatch-group attribution (lint/sentinel seam).
# ----------------------------------------------------------------------
#: Per-thread label of the dispatch group currently executing — published
#: by both executors so observability layers (``repro.lint.sentinel``'s
#: blocking-read attribution) can blame a device→host stall on the group
#: that performed it without the executors knowing the sentinel exists.
#: Thread-local because the deadline scheduler runs whole groups on pool
#: threads concurrently.
_dispatch_context = threading.local()


def current_group_label() -> str | None:
    """The calling thread's active dispatch-group label (e.g.
    ``"pipelined:finish:3"``), or ``None`` outside any group scope."""
    return getattr(_dispatch_context, "label", None)


@contextlib.contextmanager
def _group_scope(label: str):
    prev = getattr(_dispatch_context, "label", None)
    _dispatch_context.label = label
    try:
        yield
    finally:
        _dispatch_context.label = prev


@contextlib.contextmanager
def _group_span(label: str):
    """One dispatch group's phase (``repro.exec.group``), which also opens
    the group's label scope."""
    with spans.span("repro.exec.group", group=label), _group_scope(label):
        yield


# ----------------------------------------------------------------------
# Results + stats (moved from repro.core.engine; engine re-exports).
# ----------------------------------------------------------------------
@dataclasses.dataclass
class ResultSet:
    """Flat result arrays: one row per (entry segment, query segment, interval)."""

    entry_idx: np.ndarray    # global index into the sorted database
    entry_traj: np.ndarray   # trajectory id of the entry segment
    entry_seg: np.ndarray    # segment id of the entry segment
    query_idx: np.ndarray    # global index into the sorted query array
    t_enter: np.ndarray
    t_exit: np.ndarray

    def __len__(self) -> int:
        return int(self.entry_idx.shape[0])

    @staticmethod
    def empty() -> "ResultSet":
        zi = np.zeros(0, np.int64)
        zf = np.zeros(0, np.float32)
        return ResultSet(zi, zi.copy(), zi.copy(), zi.copy(), zf, zf.copy())

    @staticmethod
    def concatenate(parts: list["ResultSet"]) -> "ResultSet":
        if not parts:
            return ResultSet.empty()
        return ResultSet(*[np.concatenate([getattr(p, f.name) for p in parts])
                           for f in dataclasses.fields(ResultSet)])

    def sorted_canonical(self) -> "ResultSet":
        """Canonical (entry_idx, query_idx) order — for set comparisons."""
        order = np.lexsort((self.query_idx, self.entry_idx))
        return ResultSet(*[getattr(self, f.name)[order]
                           for f in dataclasses.fields(ResultSet)])


@dataclasses.dataclass
class BatchStats:
    """Per-invocation record (feeds the §8 performance model).

    ``kernel_seconds`` is the host wall of the batch's first invocation,
    from the dispatch call until its outputs were ready and its count read
    (the ``repro.engine.dispatch`` and ``repro.exec.sync`` spans of the
    sync executor).  It is not device time: host slicing, the upload and
    the wait all count.  ``retry_seconds`` is the wall time of overflow
    re-dispatches (``repro.exec.retry``), kept separate so perf-model fits
    see clean per-invocation numbers.  Pipelined execution reports
    ``kernel_seconds`` as zero per batch (see ``ExecStats.sync_seconds``)
    and shares each group's retry wall among its retried batches.
    """

    batch_size: int
    num_candidates: int
    num_interactions: int
    num_hits: int
    kernel_seconds: float
    retries: int
    retry_seconds: float = 0.0
    #: kernel grid tiles the in-kernel spatial early-out skipped / total
    #: (PR 5; zero on paths without a tile loop — dense compaction, jnp).
    pruned_tiles: int = 0
    num_tiles: int = 0


@dataclasses.dataclass
class ExecStats:
    plan_seconds: float
    total_seconds: float
    batches: list[BatchStats]
    #: host↔device synchronization points (count reads / block_until_ready):
    #: one per invocation (+retries) in sync mode; ≤ 2 per dispatch group in
    #: pipelined mode — ≤ 2 per query set with the default single group.
    num_syncs: int = 0
    pipelined: bool = False
    #: dispatch groups the executor processed (1 = classic whole-plan phase).
    num_groups: int = 1
    #: interactions the *planner's* spatial pruning removed before dispatch
    #: (candidate sub-range trimming — ``QueryPlan.pruned_interactions``);
    #: the in-kernel tile early-out is accounted per batch in
    #: ``BatchStats.pruned_tiles`` / :attr:`pruned_tiles`.
    pruned_interactions: int = 0
    #: degradation-ladder steps taken while producing this result (PR 10):
    #: populated by the serving broker when repeated failures forced a
    #: compaction / backend / pruning / route downgrade.  Empty on every
    #: clean execution.
    degradations: list = dataclasses.field(default_factory=list)
    #: host-clock seconds per span name (``repro.core.spans``), summed over
    #: the execution; under ``TrajectoryDB.query`` it also holds the
    #: facade's and the planner's spans of the same call.
    span_seconds: dict = dataclasses.field(default_factory=dict)
    #: the execution's counters: ``dispatches`` (first dispatches),
    #: ``retried_dispatches`` (batches that needed an overflow
    #: re-dispatch), ``h2d_bytes`` (host arrays handed to the kernels' jit
    #: call, retries included), ``result_slots`` / ``result_rows``
    #: (result-buffer slots copied back / rows kept).
    counts: dict = dataclasses.field(default_factory=dict)

    @property
    def dispatch_seconds(self) -> float:
        """Wall time of the first dispatches (``repro.engine.dispatch``):
        host slicing, the jit call's enqueue and upload."""
        return self.span_seconds.get("repro.engine.dispatch", 0.0)

    @property
    def sync_seconds(self) -> float:
        """Wall time of the first waits on each dispatch group's (sync
        mode: each batch's) outputs, with their count reads and overflow
        checks (``repro.exec.sync``)."""
        return self.span_seconds.get("repro.exec.sync", 0.0)

    @property
    def pruned_tiles(self) -> int:
        return sum(b.pruned_tiles for b in self.batches)

    @property
    def total_tiles(self) -> int:
        return sum(b.num_tiles for b in self.batches)

    @property
    def kernel_seconds(self) -> float:
        """Host wall until the first invocations' outputs were ready: the
        batches' ``kernel_seconds`` in sync mode, the group waits
        (:attr:`sync_seconds`) in pipelined mode.  Not device time — the
        kernel's device time is only in a profiler trace.  Retry
        re-dispatch time is excluded so perf-model fits see
        per-invocation numbers; it is accounted in :attr:`retry_seconds`."""
        if self.pipelined:
            return self.sync_seconds
        return sum(b.kernel_seconds for b in self.batches)

    @property
    def retry_seconds(self) -> float:
        return sum(b.retry_seconds for b in self.batches)

    @property
    def host_seconds(self) -> float:
        """Wall time outside :attr:`kernel_seconds` and
        :attr:`retry_seconds` — both host walls that include the device's
        work, so this is host work the device did not overlap."""
        return self.total_seconds - self.kernel_seconds - self.retry_seconds

    @property
    def total_interactions(self) -> int:
        return sum(b.num_interactions for b in self.batches)

    @property
    def total_hits(self) -> int:
        return sum(b.num_hits for b in self.batches)

    @property
    def num_invocations(self) -> int:
        return len(self.batches)

    @property
    def total_retries(self) -> int:
        return sum(b.retries for b in self.batches)


# ----------------------------------------------------------------------
# Dispatcher protocol.
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Dispatch:
    """One in-flight batch dispatch: the batch, its result capacity, the
    blockable device outputs, and optional dispatcher-private context
    (e.g. the sharded dispatcher's per-pod layout)."""

    batch: QueryBatch
    capacity: int
    out: object
    ctx: object = None


@runtime_checkable
class BatchDispatcher(Protocol):
    """One device-execution strategy, bound to a query set + threshold.

    ``dispatch`` must be asynchronous (no host reads); ``count`` /
    ``retry_capacity`` / ``marshal`` are only called after the executor has
    blocked on ``Dispatch.out``.

    A dispatcher may additionally expose ``redispatch(dp, capacity)`` — an
    overflow re-dispatch of the same batch at a larger capacity that can
    reuse ``dp.ctx`` (prepared host inputs) instead of rebuilding them;
    executors fall back to ``dispatch(dp.batch, capacity)`` when absent.
    """

    def dispatch(self, batch: QueryBatch, capacity: int) -> Dispatch: ...

    def count(self, dp: Dispatch) -> int: ...

    def retry_capacity(self, dp: Dispatch) -> int | None: ...

    def marshal(self, dp: Dispatch, count: int) -> ResultSet | None: ...


def _redispatch(dispatcher: BatchDispatcher, dp: Dispatch,
                capacity: int) -> Dispatch:
    """Overflow re-dispatch, reusing prepared inputs when the dispatcher
    supports it."""
    redo = getattr(dispatcher, "redispatch", None)
    if redo is not None:
        return redo(dp, capacity)
    return dispatcher.dispatch(dp.batch, capacity)


def _tile_stats(dispatcher: BatchDispatcher, dp: Dispatch) -> tuple[int, int]:
    """(pruned_tiles, num_tiles) of a synced dispatch — an *optional*
    dispatcher hook (kernel-level spatial pruning accounting); dispatchers
    without it report zeros.  Only called after the executor has blocked on
    ``dp.out``, so reading the counters costs no extra host sync."""
    fn = getattr(dispatcher, "tile_stats", None)
    return fn(dp) if fn is not None else (0, 0)


def _record_empty(dispatcher: BatchDispatcher, batch: QueryBatch) -> None:
    """Tell the dispatcher a zero-candidate batch was skipped host-side —
    an *optional* hook (routing/accounting ledgers need an explicit
    empty record per planned batch, not a silent gap); dispatchers
    without it see nothing."""
    fn = getattr(dispatcher, "record_empty", None)
    if fn is not None:
        fn(batch)


def _empty_stats(batch: QueryBatch) -> BatchStats:
    return BatchStats(batch.size, 0, 0, 0, 0.0, 0)


def _dispatch_span(batch: QueryBatch, capacity: int) -> spans.span:
    """A batch's first dispatch (``repro.engine.dispatch``): the
    dispatcher's host slicing, the jit call's enqueue and its upload.  Its
    args name the shape, so a lowering inside it says which."""
    return spans.span("repro.engine.dispatch",
                      candidates=batch.num_candidates, queries=batch.size,
                      capacity=capacity)


def _overflows(dispatcher: BatchDispatcher, slots: dict,
               idx) -> dict[int, int]:
    """Batch index → re-dispatch capacity, for each synced dispatch in
    ``idx`` whose buffers could not hold every hit."""
    out = {}
    for i in idx:
        cap = dispatcher.retry_capacity(slots[i])
        if cap is not None:
            out[i] = cap
    return out


def _retry(dispatcher: BatchDispatcher, slots: dict, counts: dict,
           rounds: dict, over: dict[int, int],
           max_retries: int) -> tuple[int, float]:
    """Re-dispatch the overflowed batches ``over`` (index → capacity) until
    every count fits (``repro.exec.retry``), updating ``slots``, ``counts``
    and ``rounds`` in place.  Exact counts make one retry sufficient on
    honest devices, so ``max_retries`` only bites when counts are
    corrupted or capacities adversarial.  Returns (host syncs, seconds)."""
    spans.count("retried_dispatches", len(over))
    syncs = 0
    with spans.span("repro.exec.retry", batches=len(over)) as sp:
        while over:
            for i, cap in over.items():
                if rounds.get(i, 0) >= max_retries:
                    raise CapacityError(counts[i], slots[i].capacity,
                                        batch_index=i,
                                        retries=rounds.get(i, 0))
                rounds[i] = rounds.get(i, 0) + 1
                slots[i] = _redispatch(dispatcher, slots[i], cap)
            jax.block_until_ready([slots[i].out for i in over])
            syncs += 1
            for i in over:
                counts[i] = dispatcher.count(slots[i])
            over = _overflows(dispatcher, slots, over)
    return syncs, sp.seconds


def _marshal(dispatcher: BatchDispatcher, dp: Dispatch,
             count: int) -> ResultSet | None:
    with spans.span("repro.exec.marshal"):
        return dispatcher.marshal(dp, count)


def _concat(parts: list[ResultSet]) -> ResultSet:
    with spans.span("repro.exec.concat"):
        return ResultSet.concatenate(parts)


#: Group-completion hook ``(group_index, batch_indices, group_results)`` —
#: fired by both executors as soon as one dispatch group's results are
#: marshalled (for the pipelined executor that is while the *next* group is
#: still computing).  The incremental-delivery seam for streaming
#: consumers: ``DeadlineScheduler.execute(on_group=...)`` exposes it with
#: first-completion deduplication.  (The serving broker delivers slices by
#: running one single-group sub-plan per pump step instead — see
#: ``repro.serve.broker``.)
GroupHook = Callable[[int, "list[int]", ResultSet], None]


# ----------------------------------------------------------------------
# Executors.
# ----------------------------------------------------------------------
class SyncExecutor:
    """Classic per-batch loop: dispatch → sync → (maybe retry) → next.

    Used for §8 perf-model fits, which need per-invocation timings — the
    pipelined executor deliberately makes those unobservable.
    """

    pipelined = False

    def __init__(self, dispatcher: BatchDispatcher, *,
                 on_group: GroupHook | None = None,
                 max_capacity_retries: int = 3):
        self.dispatcher = dispatcher
        self.on_group = on_group
        self.max_capacity_retries = int(max_capacity_retries)

    def _run_batch(self, i: int, batch: QueryBatch, capacity: int
                   ) -> tuple[ResultSet | None, BatchStats, int]:
        """Dispatch, wait, retry and marshal batch ``i``; returns its part,
        its stats and the host syncs it took."""
        disp = self.dispatcher
        spans.count("dispatches")
        with _dispatch_span(batch, capacity) as first:
            slots = {i: disp.dispatch(batch, capacity)}
        with spans.span("repro.exec.sync") as wait:
            jax.block_until_ready(slots[i].out)
            counts = {i: disp.count(slots[i])}
            over = _overflows(disp, slots, (i,))
        rounds: dict[int, int] = {}
        syncs, retry_s = (_retry(disp, slots, counts, rounds, over,
                                 self.max_capacity_retries)
                          if over else (0, 0.0))
        part = _marshal(disp, slots[i], counts[i])
        pt, nt = _tile_stats(disp, slots[i])
        return part, BatchStats(
            batch.size, batch.num_candidates,
            batch.size * batch.num_candidates, counts[i],
            first.seconds + wait.seconds, rounds.get(i, 0), retry_s,
            pruned_tiles=pt, num_tiles=nt), 1 + syncs

    def run(self, plan: QueryPlan) -> tuple[ResultSet, ExecStats]:
        disp = self.dispatcher
        nb = plan.num_batches
        groups = plan.groups if plan.groups else (
            [list(range(nb))] if nb else [])
        parts: list[ResultSet] = []
        stats_by_idx: dict[int, BatchStats] = {}
        num_syncs = 0
        with spans.recording() as rec:
            with spans.span("repro.exec.run") as whole:
                for gi, g in enumerate(groups):
                    group_parts: list[ResultSet] = []
                    with _group_span(f"sync:{gi}"):
                        for i in g:
                            batch = plan.batches[i]
                            if batch.num_candidates == 0:
                                _record_empty(disp, batch)
                                stats_by_idx[i] = _empty_stats(batch)
                                continue
                            part, stats_by_idx[i], syncs = self._run_batch(
                                i, batch, plan.capacities[i])
                            num_syncs += syncs
                            if part is not None:
                                group_parts.append(part)
                    parts.extend(group_parts)
                    if self.on_group is not None:
                        self.on_group(gi, list(g), _concat(group_parts))
            stats = [stats_by_idx[i] for i in range(nb)]
            rs = _concat(parts)
        return rs, ExecStats(
            plan.plan_seconds, whole.seconds, stats, num_syncs=num_syncs,
            pipelined=False, num_groups=max(plan.num_groups, 1),
            pruned_interactions=getattr(plan, "pruned_interactions", 0),
            span_seconds=rec.seconds, counts=rec.counts)


class PipelinedExecutor:
    """Two-phase group-wise executor: dispatch everything in a group, sync
    once, retry only overflows, and marshal while the *next* group computes.

    Per group: phase A queues every batch's device computation via JAX
    async dispatch (no host reads, so the host never stalls between
    batches); phase B performs one ``block_until_ready`` over the group,
    reads every exact count, re-dispatches only the overflowed batches at
    enlarged (≥ doubled, bucketed) capacity, and syncs those once more —
    ≤ 2 host syncs per group, ≤ 2 per query set with the default
    single-group plan.  Group k's phase B (including host-side result
    marshalling) runs *after* group k+1's phase A, so assembly of group k
    overlaps device compute of group k+1.
    """

    pipelined = True

    def __init__(self, dispatcher: BatchDispatcher, *,
                 on_group: GroupHook | None = None,
                 max_capacity_retries: int = 3):
        self.dispatcher = dispatcher
        self.on_group = on_group
        self.max_capacity_retries = int(max_capacity_retries)

    def run(self, plan: QueryPlan) -> tuple[ResultSet, ExecStats]:
        disp = self.dispatcher
        nb = plan.num_batches
        groups = plan.groups if plan.groups else (
            [list(range(nb))] if nb else [])
        slots: dict[int, Dispatch] = {}
        counts: dict[int, int] = {}
        retried: dict[int, float] = {}     # batch idx -> retry wall share
        rounds: dict[int, int] = {}        # batch idx -> overflow retries
        parts: dict[int, ResultSet] = {}
        num_syncs = 0

        def dispatch_group(gi: int, g: list[int]) -> None:
            with _group_span(f"pipelined:dispatch:{gi}"):
                for i in g:
                    batch = plan.batches[i]
                    if batch.num_candidates == 0:
                        _record_empty(disp, batch)
                        continue
                    spans.count("dispatches")
                    with _dispatch_span(batch, plan.capacities[i]):
                        slots[i] = disp.dispatch(batch, plan.capacities[i])

        def finish_group(gi: int, g: list[int]) -> None:
            nonlocal num_syncs
            live = [i for i in g if i in slots]
            if not live:
                if self.on_group is not None:
                    self.on_group(gi, list(g), ResultSet.empty())
                return
            with _group_span(f"pipelined:finish:{gi}"):
                with spans.span("repro.exec.sync"):
                    jax.block_until_ready([slots[i].out for i in live])
                    num_syncs += 1
                    for i in live:
                        counts[i] = disp.count(slots[i])
                    over = _overflows(disp, slots, live)
                # Re-dispatch only overflowed batches.
                if over:
                    syncs, retry_s = _retry(disp, slots, counts, rounds,
                                            over, self.max_capacity_retries)
                    num_syncs += syncs
                    for i in over:
                        retried[i] = retry_s / len(over)
                # Host-side marshalling — by now the next group's phase A
                # has already queued its device work, so this overlaps
                # compute.
                for i in live:
                    part = _marshal(disp, slots[i], counts[i])
                    if part is not None:
                        parts[i] = part
            if self.on_group is not None:
                self.on_group(gi, list(g), _concat(
                    [parts[i] for i in g if i in parts]))

        with spans.recording() as rec:
            with spans.span("repro.exec.run") as whole:
                for gi, g in enumerate(groups):
                    dispatch_group(gi, g)
                    if gi > 0:
                        finish_group(gi - 1, groups[gi - 1])
                if groups:
                    finish_group(len(groups) - 1, groups[-1])

                stats = []
                for i, batch in enumerate(plan.batches):
                    if batch.num_candidates == 0:
                        stats.append(_empty_stats(batch))
                        continue
                    pt, nt = (_tile_stats(disp, slots[i]) if i in slots
                              else (0, 0))
                    stats.append(BatchStats(
                        batch.size, batch.num_candidates,
                        batch.size * batch.num_candidates, counts.get(i, 0),
                        0.0, rounds.get(i, 0), retried.get(i, 0.0),
                        pruned_tiles=pt, num_tiles=nt))
            rs = _concat([parts[i] for i in sorted(parts)])
        return rs, ExecStats(
            plan.plan_seconds, whole.seconds, stats, num_syncs=num_syncs,
            pipelined=True, num_groups=max(len(groups), 1),
            pruned_interactions=getattr(plan, "pruned_interactions", 0),
            span_seconds=rec.seconds, counts=rec.counts)


def make_executor(dispatcher: BatchDispatcher, *, pipeline: bool,
                  on_group: GroupHook | None = None,
                  max_capacity_retries: int = 3):
    """The executor for ``pipeline=True`` (two-phase, O(1) syncs per group)
    or ``pipeline=False`` (per-batch sync loop with observable timings).
    ``on_group`` fires as each dispatch group's results are marshalled.
    ``max_capacity_retries`` bounds overflow re-dispatches per batch;
    exceeding it raises :class:`~repro.core.errors.CapacityError`."""
    cls = PipelinedExecutor if pipeline else SyncExecutor
    return cls(dispatcher, on_group=on_group,
               max_capacity_retries=max_capacity_retries)


__all__ = [
    "BatchDispatcher", "BatchStats", "Dispatch", "ExecStats", "GroupHook",
    "PipelinedExecutor", "ResultSet", "SyncExecutor", "make_executor",
]
