"""End-to-end distance-threshold query engine (paper §4–§5).

Pipeline per the paper's "general approach" (§4): the host keeps the
sorted entry segments, the temporal-bin index and the sorted query set;
queries are partitioned into batches (see ``repro.core.batching``); for
each batch the host computes the contiguous candidate index range from the
bins, slices that range and the batch's query segments out of its packed
arrays, and dispatches one device computation comparing the two, which
uploads both slices anew.

PR 3 split this module's former responsibilities three ways:

* **planning** (batching algorithm, capacity sizing, dispatch grouping)
  lives in ``repro.core.planner`` — the engine consumes a ``QueryPlan``
  (legacy ``BatchPlan`` arguments are coerced via ``as_query_plan``);
* **execution** (the per-batch sync loop and the two-phase pipelined
  dispatch with its overflow-retry protocol) lives in
  ``repro.core.executor`` — shared with the sharded mesh backend
  (``repro.core.distributed.ShardedEngine``);
* this module keeps the **single-device dispatcher**: slicing the packed
  segment arrays, the async ``ops.query_block`` dispatch, and host-side
  result marshalling — plus the public ``DistanceThresholdEngine`` shell.

TPU adaptations on top of the paper (see the executor/planner modules for
the mechanics):

* **Shape bucketing.**  The GPU pays a per-invocation overhead Θ; the XLA
  analogue is *compilation* of every new shape.  Result capacities round up
  to power-of-two buckets (``planner.bucket_capacity``).  With the engine's
  ``dispatch_ladder`` set (``ExecutionPolicy.dispatch_ladder``), the
  candidate and query slices are padded too, to whole tiles on a ladder of
  that many lengths per octave (``planner.bucket_rows``), with pad rows
  that can never hit (``ops.pad_block``): a query set whose batches have
  thousands of lengths then lowers tens of shapes.  Unset, the slices go
  out as they are, one shape per distinct length.
* **Overflow-retry result buffers.**  The paper statically allocates |D|
  result slots (§5).  We allocate ``capacity`` slots per batch and retry
  with doubled (bucketed) capacity on overflow — the kernel always reports
  the *exact* hit count, so a retry converges after a single re-dispatch.
* **Async pipelined execution** (``pipeline=True``, the default): ≤ 2 host
  syncs per dispatch group (one group per query set by default) instead of
  one per batch, with host marshalling of group k overlapped with device
  compute of group k+1.  ``pipeline=False`` keeps the classic per-batch
  sync loop (used by the §8 perf-model fits, which need per-invocation
  timings — see ``BatchStats``).
* **Deterministic output.**  Results are emitted in a deterministic
  per-batch order (row-major for dense compaction; tile-then-row-major for
  fused — see ``repro.kernels.ops``), concatenated in batch order.
"""
from __future__ import annotations

import numpy as np

from repro import faults
from repro.core import spans
from repro.core.batching import BatchPlan
from repro.core.executor import (BatchStats, Dispatch,  # noqa: F401 (stable re-exports)
                                 ExecStats, ResultSet, make_executor)
from repro.core.index import DEFAULT_NUM_BINS, TemporalBinIndex
from repro.core.planner import (QueryPlan, as_query_plan,
                                bucket_capacity as _bucket, bucket_rows)
from repro.core.segments import SegmentArray
from repro.kernels import ops
from repro.kernels.distthresh import (DEFAULT_CAND_BLK, DEFAULT_QRY_BLK,
                                      resolve_interpret)


def check_ladder(steps: int | None) -> int | None:
    """``steps`` as a dispatch ladder: None, or a positive count of steps
    per octave."""
    if steps is not None and (isinstance(steps, bool) or int(steps) != steps
                              or steps < 1):
        raise ValueError(f"dispatch_ladder must be None or a positive "
                         f"number of steps per octave, not {steps!r}")
    return steps


class _QueryBlockDispatcher:
    """Single-device dispatcher: contiguous host slices → ``query_block``.

    Implements the ``repro.core.executor.BatchDispatcher`` protocol for one
    (engine, query set, threshold) binding.
    """

    def __init__(self, engine: "DistanceThresholdEngine",
                 q_packed: np.ndarray, d: float):
        self.engine = engine
        self.q_packed = q_packed
        self.d = d
        if engine.dispatch_ladder:
            self._pad_e, self._pad_q = ops.pad_instants(
                engine.db.temporal_extent[1], q_packed)

    def dispatch(self, batch, capacity: int) -> Dispatch:
        eng = self.engine
        if faults.armed():
            faults.inject("engine.dispatch", q_first=int(batch.q_first),
                          use_pallas=eng.use_pallas,
                          compaction=eng.compaction)
        # Hierarchical pruning plans box-level sub-ranges in the index's
        # *permuted* segment order, so the dispatched slices come from the
        # permuted packed copy (identical to ``_packed`` when K=1).
        packed = (eng._packed_perm if eng.pruning == "hierarchical"
                  else eng._packed)
        e_slice = packed[batch.cand_first:batch.cand_last + 1]
        q_slice = self.q_packed[batch.q_first:batch.q_last + 1]
        real = (e_slice.shape[0], q_slice.shape[0])
        valid_rows = None
        if eng.dispatch_ladder:
            # Both slices padded onto the ladder, entry pads and query pads
            # at instants of their own beyond every real one: entry_idx
            # still indexes the real slice, since pads never hit.
            e_slice = ops.pad_block(e_slice, bucket_rows(
                real[0], eng.cand_blk, eng.dispatch_ladder), self._pad_e)
            q_slice = ops.pad_block(q_slice, bucket_rows(
                real[1], eng.qry_blk, eng.dispatch_ladder), self._pad_q)
            valid_rows = real
        rows = e_slice.shape[0] + q_slice.shape[0]
        spans.count("dispatched_rows", rows)
        spans.count("pad_rows", rows - sum(real))
        out = ops.query_block(
            e_slice, q_slice, np.float32(self.d), capacity=capacity,
            use_pallas=eng.use_pallas, interpret=eng.interpret,
            cand_blk=eng.cand_blk, qry_blk=eng.qry_blk,
            compaction=eng.compaction, pruning=eng.pruning,
            valid_rows=valid_rows)
        return Dispatch(batch, capacity, out)

    def count(self, dp: Dispatch) -> int:
        count = int(dp.out["count"])
        if faults.armed():
            count = faults.corrupt("engine.count", count,
                                   q_first=int(dp.batch.q_first))
        return count

    def tile_stats(self, dp: Dispatch) -> tuple[int, int]:
        """Kernel-level pruning counters (executor hook; see
        ``repro.core.executor._tile_stats``)."""
        return int(dp.out["pruned_tiles"]), int(dp.out["num_tiles"])

    def retry_capacity(self, dp: Dispatch) -> int | None:
        count = self.count(dp)
        return _bucket(count) if count > dp.capacity else None

    def marshal(self, dp: Dispatch, count: int) -> ResultSet | None:
        if faults.armed():
            faults.inject("engine.marshal", q_first=int(dp.batch.q_first))
        batch, out, db = dp.batch, dp.out, self.engine.db
        # Mask on the buffer's -1 pads (every kernel variant initializes the
        # index buffers to -1) instead of trusting ``count``: a corrupted
        # overflow count then costs at most one spurious bounded retry — it
        # can never leak pad rows into results nor drop real ones.
        with spans.span("repro.engine.fetch"):
            e_buf = np.asarray(out["entry_idx"])
        spans.count("result_slots", e_buf.size)
        keep = e_buf >= 0
        rows = int(np.count_nonzero(keep))
        if not rows:
            return None
        spans.count("result_rows", rows)
        with spans.span("repro.engine.fetch"):
            q_buf, t_enter, t_exit = (np.asarray(out[k]) for k in (
                "query_idx", "t_enter", "t_exit"))
        e_local = e_buf[keep]
        q_local = q_buf[keep]
        e_global = batch.cand_first + e_local.astype(np.int64)
        if self.engine.pruning == "hierarchical":
            perm = self.engine.index.perm
            if perm is not None:
                # Permuted dispatch position → original sorted-db index, so
                # results stay byte-identical across pruning modes.
                e_global = perm[e_global]
        return ResultSet(
            entry_idx=e_global,
            entry_traj=db.traj_id[e_global].astype(np.int64),
            entry_seg=db.seg_id[e_global].astype(np.int64),
            query_idx=batch.q_first + q_local.astype(np.int64),
            t_enter=t_enter[keep],
            t_exit=t_exit[keep],
        )


class DistanceThresholdEngine:
    """In-memory distance-threshold query engine over a trajectory database."""

    def __init__(self, db: SegmentArray, *, num_bins: int = DEFAULT_NUM_BINS,
                 use_pallas: bool = False, interpret: bool | None = None,
                 cand_blk: int = DEFAULT_CAND_BLK, qry_blk: int = DEFAULT_QRY_BLK,
                 default_capacity: int = 4096, compaction: str = "fused",
                 pipeline: bool = True, pruning: str = "spatial",
                 index_kboxes: int = 1, max_capacity_retries: int = 3,
                 dispatch_ladder: int | None = None):
        """``use_pallas=False`` routes interactions through the jnp oracle —
        the right default on CPU where Pallas runs in interpret mode.  Both
        paths share identical semantics (tests assert equality).
        ``interpret=None`` resolves from the default device
        (``repro.kernels.distthresh.resolve_interpret``): the Pallas
        interpreter on a CPU, the compiled kernel on a TPU.

        ``compaction`` selects the result-compaction strategy ("fused" uses
        the in-kernel compaction kernel on the Pallas path — see
        ``repro.kernels.ops``; "dense" forces the two-phase pass; the jnp
        oracle is always dense).  ``pipeline`` selects the async
        two-phase executor (see the module docstring); both can be
        overridden per call on :meth:`execute`.

        ``pruning="spatial"`` (the default) arms the fused kernels'
        tile-level MBR early-out (work-only — the result set is provably
        unchanged); the planner-level candidate trimming lives upstream in
        ``repro.core.planner`` and reaches this engine through the plan.
        ``pruning="hierarchical"`` plans against the K-box-per-bin level
        and dispatches with the live-tile kernel; its plans address the
        index's *permuted* segment order, so plan and engine must agree on
        the pruning mode (the facade guarantees it; direct engine users
        own that consistency).  ``index_kboxes`` is the per-bin spatial
        split factor K handed to ``TemporalBinIndex.build`` — structural
        (the default K=1 makes hierarchical planning degenerate to
        bin-level boxes while keeping the live-tile kernel dispatch).

        ``dispatch_ladder`` (steps per octave) pads every dispatch's
        candidate and query slices onto ``planner.bucket_rows``'s ladder
        (see the module docstring); ``None`` dispatches exact slices.
        """
        if compaction not in ops.COMPACTIONS:
            raise ValueError(f"unknown compaction {compaction!r}; "
                             f"choose from {ops.COMPACTIONS}")
        if pruning not in ops.PRUNINGS:
            raise ValueError(f"unknown pruning {pruning!r}; "
                             f"choose from {ops.PRUNINGS}")
        self.db = db if db.is_sorted() else db.sort_by_tstart()
        self.index = TemporalBinIndex.build(self.db, num_bins,
                                            kboxes=index_kboxes)
        self._packed = self.db.packed()          # (n, 8) float32, host copy
        # Permuted device layout for hierarchical (box-level) plans: row i
        # holds the segment at sorted-db position perm[i].  Alias when K=1.
        self._packed_perm = (self._packed if self.index.perm is None
                             else self._packed[self.index.perm])
        self.use_pallas = use_pallas
        self.interpret = resolve_interpret(interpret)
        self.cand_blk = cand_blk
        self.qry_blk = qry_blk
        self.default_capacity = default_capacity
        self.compaction = compaction
        self.pipeline = pipeline
        self.pruning = pruning
        # Bounded overflow-retry (PR 10): batches whose hits still exceed
        # capacity after this many doublings raise CapacityError.
        self.max_capacity_retries = int(max_capacity_retries)
        self.dispatch_ladder = check_ladder(dispatch_ladder)

    # ------------------------------------------------------------------
    def dispatcher(self, queries_packed: np.ndarray,
                   d: float) -> _QueryBlockDispatcher:
        """The engine's ``BatchDispatcher`` for one query set (executor
        protocol interop — the scheduler and tests drive it directly)."""
        return _QueryBlockDispatcher(self, queries_packed, float(d))

    # ------------------------------------------------------------------
    def execute(self, queries: SegmentArray, d: float,
                plan: BatchPlan | QueryPlan,
                *, pipeline: bool | None = None,
                on_group=None) -> tuple[ResultSet, ExecStats]:
        """Run every batch in ``plan`` against the database.

        ``plan`` may be a refined ``QueryPlan`` (the facade's planner
        output, carrying capacities + dispatch groups) or a legacy
        ``BatchPlan`` (coerced to a single-group plan sized by the engine's
        ``default_capacity``).  ``pipeline`` overrides the engine-level
        default for this call (``None`` → use ``self.pipeline``);
        ``on_group`` is the executor's group-completion hook (incremental
        result delivery — see ``repro.core.executor.GroupHook``).
        """
        if not queries.is_sorted():
            # Unreachable from the public facade: repro.api.TrajectoryDB
            # sorts queries before planning/execution.  Kept as a guard for
            # direct engine users, who own the sortedness precondition.
            raise ValueError(
                "queries must be sorted by t_start; use "
                "repro.api.TrajectoryDB.query, which sorts automatically")
        qplan = as_query_plan(plan, default_capacity=self.default_capacity)
        use_pipeline = self.pipeline if pipeline is None else pipeline
        executor = make_executor(self.dispatcher(queries.packed(), d),
                                 pipeline=use_pipeline, on_group=on_group,
                                 max_capacity_retries=getattr(
                                     self, "max_capacity_retries", 3))
        return executor.run(qplan)


# ----------------------------------------------------------------------
# Brute-force oracle (for tests): all pairs, no index, chunked.
# ----------------------------------------------------------------------
def brute_force(db: SegmentArray, queries: SegmentArray, d: float,
                chunk: int = 2048) -> ResultSet:  # lint: ignore[SYNC001] — synchronous oracle; per-chunk host reads are its contract, not a pipeline leak
    """All-pairs reference: compares every entry to every query segment."""
    db_packed = db.packed()
    q_packed = queries.packed()
    parts: list[ResultSet] = []
    for c0 in range(0, len(db), chunk):
        e_slice = db_packed[c0:c0 + chunk]
        t_enter, t_exit, hit = ops.interaction_tiles(
            e_slice, q_packed, np.float32(d), use_pallas=False)
        hit = np.asarray(hit)
        if not hit.any():
            continue
        ei, qi = np.nonzero(hit)
        eg = c0 + ei.astype(np.int64)
        parts.append(ResultSet(
            entry_idx=eg,
            entry_traj=db.traj_id[eg].astype(np.int64),
            entry_seg=db.seg_id[eg].astype(np.int64),
            query_idx=qi.astype(np.int64),
            t_enter=np.asarray(t_enter)[ei, qi],
            t_exit=np.asarray(t_exit)[ei, qi],
        ))
    return ResultSet.concatenate(parts).sorted_canonical()
