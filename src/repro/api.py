"""Unified query facade for the trajectory database (the stable public API).

The paper's deliverable is a *query service* (§3): given a trajectory
database ``D``, find every trajectory that comes within distance ``d`` of a
search trajectory during its temporal extent, for an online stream of such
queries.  The lower layers of this repo expose the machinery — the
temporal-bin index (``repro.core.index``), batch-generation algorithms
(``repro.core.batching``), the accelerator engine (``repro.core.engine``),
the R-tree CPU baseline (``repro.core.rtree``) and the deadline scheduler
(``repro.core.scheduler``) — but each with its own calling convention and
preconditions (pre-sorted queries, manual plan construction, reaching into
``engine.index``).

:class:`TrajectoryDB` is the single front door over all of them:

* ``TrajectoryDB.from_segments(db)`` / ``TrajectoryDB.from_scenario("S2")``
  own sorting and index construction — callers never see the sortedness
  precondition.
* ``db.query(queries, d, backend=..., batching=...)`` plans, executes and
  returns a :class:`QueryResult` whose ``query_idx`` refers to the
  **caller's original query order** (the raw engine indexes the internally
  sorted array — a silent off-by-permutation trap this facade removes).
* Execution strategy is pluggable via the :class:`QueryBackend` protocol:
  ``"pallas"`` (the TPU kernel: compiled on a TPU, interpreted on a
  CPU), ``"jnp"`` (the XLA
  oracle — the right default on CPU), ``"rtree"`` (the paper's §7.3
  search-and-refine CPU baseline), ``"brute"`` (the all-pairs oracle) and
  ``"shard"`` (the temporal-pod mesh backend from ``repro.core.
  distributed`` — the paper's §1 multi-node partitioning, with the same
  ≤ 2-host-syncs-per-dispatch-group pipelined dispatch as the
  single-device engine).  All five return identical canonical result sets.
* Planning and execution are split (PR 3): the facade's
  :class:`~repro.core.planner.QueryPlanner` turns a policy + query set into
  a ``QueryPlan`` (batches, capacities, dispatch groups) that every
  backend's executor consumes — see ``repro.core.planner`` /
  ``repro.core.executor``.
* Tuning knobs live in one :class:`ExecutionPolicy` value object instead of
  being scattered across constructors and free functions.
* ``db.query_stream(...)`` routes execution through the deadline/re-issue
  scheduler (``repro.core.scheduler``), for every engine backend — since
  PR 4 ``backend="shard"`` streams through the per-pod routing layer.
* ``db.broker(...)`` returns the session-oriented serving front door
  (``repro.serve.broker.QueryBroker``): ticketed async submit, a
  ``step()`` pump executing one dispatch group at a time, incremental
  per-group result slices, §8-model admission control and per-pod shard
  routing.  ``QueryBroker`` / ``QueryTicket`` / ``GroupSlice`` /
  ``AdmissionError`` / ``DeadlineExceededError`` are re-exported here.

Quick example::

    from repro.api import TrajectoryDB

    db = TrajectoryDB.from_scenario("S2", scale=0.02)
    result = db.query(db.scenario_queries, db.scenario_d, backend="jnp")
    for traj in result.matched_trajectories():
        ...
"""
from __future__ import annotations

import copy
import dataclasses
import math
from typing import Callable, Mapping, Protocol, runtime_checkable

import numpy as np

from repro.core import spans
from repro.core.batching import ALGORITHMS, BatchPlan
from repro.core.engine import (DistanceThresholdEngine, ExecStats, ResultSet,
                               brute_force, check_ladder)
from repro.core.errors import CapacityError, PodFailedError
from repro.core.index import DEFAULT_NUM_BINS, TemporalBinIndex
from repro.core.planner import PRUNINGS, QueryPlan, QueryPlanner
from repro.core.rtree import RTreeEngine
from repro.core.scheduler import DeadlineScheduler, SchedulerStats
from repro.core.segments import SegmentArray
from repro.kernels.distthresh import (DEFAULT_CAND_BLK, DEFAULT_QRY_BLK,
                                      resolve_interpret)

#: Names accepted by ``TrajectoryDB.query(backend=...)``.
BACKENDS = ("pallas", "jnp", "rtree", "brute", "shard")

#: Backends that execute through a ``repro.core.executor`` driver (and
#: therefore consume a ``QueryPlan`` and report ``ExecStats``).
ENGINE_BACKENDS = ("pallas", "jnp", "shard")

#: Default batch size anchor used when an algorithm's parameters are not
#: given explicitly (the paper's practical PERIODIC recommendation, §7.4).
DEFAULT_BATCH_SIZE = 64


# ----------------------------------------------------------------------
# Execution policy: every tuning knob in one value object.
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """How a query should be executed — algorithm, kernel and scheduling
    parameters.  Replaces the seed's 7-kwarg engine constructor plus the
    per-call-site batching arguments.

    Only the fields relevant to the chosen backend are consulted (e.g.
    ``rtree_*`` only for ``backend="rtree"``).  ``num_bins`` is structural —
    it shapes the database's temporal-bin index and is therefore consulted
    at ``TrajectoryDB`` *construction* time only; every other field may be
    overridden per call via ``db.query(..., policy=...)``.
    """

    # -- batching (engine backends) ------------------------------------
    batching: str = "greedysetsplit-min"
    batch_params: Mapping | None = None   # None → per-algorithm defaults

    # -- index ----------------------------------------------------------
    num_bins: int = DEFAULT_NUM_BINS
    #: per-bin spatial split factor K for the hierarchical index layer
    #: (PR 7).  Structural like ``num_bins`` — consulted at ``TrajectoryDB``
    #: construction.  K=1 (default) is exactly the PR 5 one-box-per-bin
    #: index; K>1 splits each temporal bin's segments into up to K spatial
    #: boxes so ``pruning="hierarchical"`` can prune multi-modal data that
    #: unions into one useless fat box per bin.
    index_kboxes: int = 1
    #: spatiotemporal candidate pruning: ``"spatial"`` (default, PR 5)
    #: prices batching against the pruned workload, trims and splits each
    #: batch's candidate range against the per-bin MBR index, and arms the
    #: fused kernels' tile-level MBR early-out; ``"hierarchical"`` (PR 7)
    #: plans at the K-box level (set ``index_kboxes`` > 1 for multi-modal
    #: wins) and replaces the per-tile box test with the device-side
    #: live-tile list kernel; ``"none"`` keeps the paper's temporal-only
    #: candidates.  Pruning is exact — canonical results are byte-identical
    #: across all modes; only the work (and hence the wall time) changes.
    pruning: str = "spatial"
    #: cap on sub-ranges one batch may split into during pruning (None →
    #: ``repro.core.index.DEFAULT_MAX_SUBRANGES``).  Surplus runs merge
    #: across the smallest gaps — exact but less pruned; the coarse pricing
    #: grid charges the batching merges for that re-admission.
    max_subranges: int | None = None

    # -- kernel / device ------------------------------------------------
    cand_blk: int = DEFAULT_CAND_BLK
    qry_blk: int = DEFAULT_QRY_BLK
    capacity: int = 4096                  # result-buffer slots per batch
    #: Pallas interpret mode; None → from the device's platform
    #: (``distthresh.resolve_interpret``: interpreted only on a CPU)
    interpret: bool | None = None
    compaction: str = "fused"             # "fused" in-kernel | "dense" 2-phase
    pipeline: bool = True                 # async 2-phase executor (O(1) syncs)
    #: executor dispatch groups per query set (None → one group = classic
    #: O(1)-sync shape; k → marshalling of group i overlaps compute of i+1)
    group_size: int | None = None
    #: bound on per-batch overflow re-dispatches (PR 10).  The kernels
    #: report exact counts so one retry normally converges; a batch still
    #: overflowing after this many enlargements raises a structured
    #: :class:`~repro.core.errors.CapacityError` carrying the exact count
    #: instead of growing (and recompiling) without bound.
    max_capacity_retries: int = 3
    #: steps per octave of the single-device dispatch-shape ladder: the
    #: engine pads each batch's candidate and query slices to whole tiles on
    #: a ladder of that many lengths per octave (padding below 1/steps), so
    #: a query set whose batches have many lengths lowers few shapes.
    #: ``None`` dispatches exact slices, one jit shape per distinct length.
    #: The shard backend always pads, on its own power-of-two ladder.
    dispatch_ladder: int | None = None

    # -- sharded mesh backend (backend="shard") -------------------------
    shard_pods: int | None = None         # None → every local device
    shard_capacity: int = 4096            # result slots per pod per batch
    #: Pallas kernels inside shard_map; None → wherever they compile
    #: (every platform but the CPU, where the mesh runs the jnp oracle)
    shard_use_pallas: bool | None = None
    shard_balance: str = "time"           # pod partition: "time" | "num_ints"
    #: Sparse routed dispatch (PR 8): pods with zero candidates for a
    #: batch short-circuit the sharded step (``lax.cond``) instead of
    #: executing full padded blocks.  Exact — results are byte-identical
    #: with it on or off; ``RoutingStats.pods_skipped`` measures the win.
    shard_sparse: bool = True

    # -- R-tree baseline ------------------------------------------------
    rtree_r: int = 12                     # segments per leaf MBB (Fig. 5)
    rtree_fanout: int = 16
    rtree_threads: int = 1                # >1 → query_parallel

    # -- brute oracle ---------------------------------------------------
    brute_chunk: int = 2048

    # -- query_stream scheduling ---------------------------------------
    stream_workers: int = 2
    stream_slack: float = 4.0
    stream_min_deadline: float = 0.05
    #: batches per scheduler worker call (None → auto, ≥ 2 when possible —
    #: each call is one pipelined dispatch over the whole group)
    stream_group_size: int | None = None

    def with_(self, **updates) -> "ExecutionPolicy":
        """Functional update (the policy itself is immutable)."""
        return dataclasses.replace(self, **updates)

    # ------------------------------------------------------------------
    def resolved_batch_params(self, num_queries: int) -> dict:
        """Fill in per-algorithm defaults anchored at DEFAULT_BATCH_SIZE."""
        if self.batching not in ALGORITHMS:
            raise ValueError(
                f"unknown batching algorithm {self.batching!r}; "
                f"choose from {sorted(ALGORITHMS)}")
        if self.batch_params:
            return dict(self.batch_params)
        s = DEFAULT_BATCH_SIZE
        return {
            "periodic": {"s": s},
            "setsplit-fixed": {"num_batches": max(num_queries // s, 1)},
            "setsplit-max": {"max_size": 2 * s},
            "setsplit-minmax": {"min_size": max(s // 2, 1), "max_size": 2 * s},
            "greedysetsplit-min": {"bound": s},
            "greedysetsplit-max": {"bound": 2 * s},
        }[self.batching]


def _shard_use_pallas(pol: ExecutionPolicy) -> bool:
    """The mesh backend's kernel choice: the policy's, or else the Pallas
    kernel wherever it compiles (every platform but the CPU)."""
    if pol.shard_use_pallas is not None:
        return bool(pol.shard_use_pallas)
    return not resolve_interpret()


# ----------------------------------------------------------------------
# Results, in the caller's query order.
# ----------------------------------------------------------------------
@dataclasses.dataclass
class QueryResult:
    """Flat result arrays, one row per (entry segment, query segment,
    temporal interval) — like ``ResultSet``, but ``query_idx`` refers to the
    **caller's** query array, not the internally sorted one, and rows are in
    canonical (query_idx, entry_idx) order regardless of backend.
    """

    entry_idx: np.ndarray    # index into the sorted database (db.segments)
    entry_traj: np.ndarray   # trajectory id of the entry segment
    entry_seg: np.ndarray    # segment id of the entry segment
    query_idx: np.ndarray    # index into the CALLER's query array
    t_enter: np.ndarray
    t_exit: np.ndarray
    d: float
    backend: str
    stats: ExecStats | None = None            # engine backends only
    plan: BatchPlan | QueryPlan | None = None  # engine backends only
    #: True when the serving stack produced this result through a
    #: degradation-ladder step (slower route, byte-identical rows) or when
    #: it is a :meth:`QueryTicket.partial_result` of an incomplete ticket.
    degraded: bool = False

    def __len__(self) -> int:
        return int(self.entry_idx.shape[0])

    # ------------------------------------------------------------------
    @staticmethod
    def from_result_set(rs: ResultSet, *, order: np.ndarray | None,
                        d: float, backend: str,
                        stats: ExecStats | None = None,
                        plan: BatchPlan | QueryPlan | None = None
                        ) -> "QueryResult":
        """Map a backend ``ResultSet`` (query_idx into the sorted query
        array) back to caller order and canonicalize row order.

        ``order`` is the sort permutation (sorted position → caller
        position); ``None`` means the caller's queries were already sorted.
        """
        with spans.span("repro.facade.canonical", rows=len(rs)):
            q_caller = (rs.query_idx if order is None
                        else order[rs.query_idx])
            rank = np.lexsort((rs.entry_idx, q_caller))
            return QueryResult(
                entry_idx=rs.entry_idx[rank],
                entry_traj=rs.entry_traj[rank],
                entry_seg=rs.entry_seg[rank],
                query_idx=q_caller[rank],
                t_enter=rs.t_enter[rank],
                t_exit=rs.t_exit[rank],
                d=d, backend=backend, stats=stats, plan=plan,
            )

    # ------------------------------------------------------------------
    def matches_for(self, query_idx: int) -> "QueryResult":
        """Rows belonging to one caller query segment."""
        m = self.query_idx == query_idx
        return QueryResult(
            self.entry_idx[m], self.entry_traj[m], self.entry_seg[m],
            self.query_idx[m], self.t_enter[m], self.t_exit[m],
            d=self.d, backend=self.backend)

    def matched_trajectories(self) -> np.ndarray:
        """Unique database trajectory ids in the result — the paper's §3
        deliverable ("finds all trajectories within distance d")."""
        return np.unique(self.entry_traj)

    def to_result_set(self) -> ResultSet:
        """Compatibility view for code still speaking ``ResultSet`` —
        note ``query_idx`` stays in caller order."""
        return ResultSet(self.entry_idx, self.entry_traj, self.entry_seg,
                         self.query_idx, self.t_enter, self.t_exit)


# ----------------------------------------------------------------------
# Backend protocol + adapters.
# ----------------------------------------------------------------------
@runtime_checkable
class QueryBackend(Protocol):
    """One execution strategy.  ``run`` receives queries already sorted by
    ``t_start`` (the facade guarantees it) and returns results whose
    ``query_idx`` indexes that sorted array."""

    name: str
    needs_plan: bool

    def run(self, queries: SegmentArray, d: float,
            plan: BatchPlan | None) -> tuple[ResultSet, ExecStats | None]:
        ...


class EngineBackend:
    """Adapter over ``DistanceThresholdEngine`` (Pallas kernel or jnp
    oracle — same engine, one flag)."""

    needs_plan = True

    def __init__(self, name: str, engine: DistanceThresholdEngine):
        self.name = name
        self.engine = engine

    def run(self, queries: SegmentArray, d: float,
            plan: BatchPlan | None) -> tuple[ResultSet, ExecStats | None]:
        if plan is None:
            raise ValueError(f"backend {self.name!r} requires a BatchPlan")
        rs, stats = self.engine.execute(queries, d, plan)
        return rs, stats


class RTreeBackend:
    """Adapter over the §7.3 search-and-refine CPU baseline."""

    name = "rtree"
    needs_plan = False

    def __init__(self, engine: RTreeEngine, *, threads: int = 1):
        self.engine = engine
        self.threads = threads

    def run(self, queries: SegmentArray, d: float,
            plan: BatchPlan | None) -> tuple[ResultSet, ExecStats | None]:
        if self.threads > 1:
            return self.engine.query_parallel(queries, d, self.threads), None
        return self.engine.query(queries, d), None


class BruteBackend:
    """Adapter over the all-pairs oracle (tests / small inputs)."""

    name = "brute"
    needs_plan = False

    def __init__(self, db: SegmentArray, *, chunk: int = 2048):
        self.db = db
        self.chunk = chunk

    def run(self, queries: SegmentArray, d: float,
            plan: BatchPlan | None) -> tuple[ResultSet, ExecStats | None]:
        return brute_force(self.db, queries, d, chunk=self.chunk), None


class ShardBackend:
    """Adapter over the temporal-pod mesh engine
    (``repro.core.distributed.ShardedEngine``) — the paper's §1 multi-node
    partitioning as a first-class ``backend="shard"``.  Shares the
    facade's sorted segments; runs through the same pipelined executor as
    the single-device engine (≤ 2 host syncs per dispatch group — one
    group per query set unless the §8-model group derivation splits a
    high-hit-volume plan)."""

    name = "shard"
    needs_plan = True

    def __init__(self, engine):
        self.engine = engine

    def run(self, queries: SegmentArray, d: float,
            plan: BatchPlan | QueryPlan | None
            ) -> tuple[ResultSet, ExecStats | None]:
        if plan is None:
            raise ValueError("backend 'shard' requires a plan")
        return self.engine.execute(queries, d, plan)


# ----------------------------------------------------------------------
# Input hardening (PR 10).  Malformed workloads fail *here*, with a clear
# message, instead of surfacing as NaN-poisoned distances, empty results,
# or shape errors deep inside a kernel.  The checks are O(n) numpy scans —
# negligible next to packing/planning — and accept every finite workload
# the generators produce (see the property test in tests/test_faults.py).
# ----------------------------------------------------------------------
def _validate_segments(segments: SegmentArray, what: str) -> None:
    """Reject NaN/Inf coordinates or timestamps and zero-length (or
    inverted) time intervals.  ``what`` names the offending input
    ("entry segments" / "queries") in the error message."""
    if len(segments) == 0:
        return
    for field, arr in (("coordinates", segments.xs),
                       ("coordinates", segments.ys),
                       ("coordinates", segments.zs),
                       ("coordinates", segments.xe),
                       ("coordinates", segments.ye),
                       ("coordinates", segments.ze),
                       ("timestamps", segments.ts),
                       ("timestamps", segments.te)):
        arr = np.asarray(arr)
        if not np.isfinite(arr).all():
            raise ValueError(
                f"{what} contain non-finite (NaN/Inf) {field}; the distance"
                f" kernels require finite inputs — clean the workload before"
                f" building/querying the database")
    bad = np.asarray(segments.te) <= np.asarray(segments.ts)
    if bad.any():
        i = int(np.argmax(bad))
        raise ValueError(
            f"{what} contain a zero-length or inverted time interval at "
            f"index {i} (t_start={float(np.asarray(segments.ts)[i])!r}, "
            f"t_end={float(np.asarray(segments.te)[i])!r}); every segment "
            f"must satisfy t_end > t_start")


def _validate_threshold(d) -> float:
    """Reject a non-finite or negative distance threshold."""
    d = float(d)
    if not math.isfinite(d) or d < 0.0:
        raise ValueError(
            f"distance threshold d must be finite and >= 0, got {d!r}")
    return d


# ----------------------------------------------------------------------
# The facade.
# ----------------------------------------------------------------------
class TrajectoryDB:
    """In-memory spatiotemporal trajectory database with one query surface.

    Construction sorts the entry segments by ``t_start`` and builds the
    temporal-bin index once; every backend shares them.  Use the
    classmethods — the bare constructor is an implementation detail.
    """

    def __init__(self, segments: SegmentArray, *,
                 policy: ExecutionPolicy | None = None):
        _validate_segments(segments, "entry segments")
        self.policy = policy or ExecutionPolicy()
        # The engine owns sorting, the index and the packed device copy;
        # the facade aliases them so there is exactly one of each.
        self._base_engine = DistanceThresholdEngine(
            segments, num_bins=self.policy.num_bins, use_pallas=False,
            interpret=self.policy.interpret, cand_blk=self.policy.cand_blk,
            qry_blk=self.policy.qry_blk,
            default_capacity=self.policy.capacity,
            compaction=self.policy.compaction, pipeline=self.policy.pipeline,
            pruning=self.policy.pruning,
            index_kboxes=self.policy.index_kboxes)
        self.segments: SegmentArray = self._base_engine.db
        self.index: TemporalBinIndex = self._base_engine.index
        #: Monotone data-version counter — result caches key on it, so
        #: any future mutation path must bump it to invalidate them.
        #: The in-memory database is immutable today, so it stays 0.
        self.data_epoch: int = 0
        self._backends: dict[str, QueryBackend] = {}
        #: fitted §8 model (see :meth:`fit_response_model`); when set it is
        #: the default ``predict_hits`` for planning and ``predict_seconds``
        #: for broker admission.
        self.response_model = None
        # Populated by from_scenario for convenience.
        self.scenario_queries: SegmentArray | None = None
        self.scenario_d: float | None = None

    # -- construction ---------------------------------------------------
    @classmethod
    def from_segments(cls, segments: SegmentArray, *,
                      policy: ExecutionPolicy | None = None) -> "TrajectoryDB":
        """Build a database from raw (possibly unsorted) segments."""
        return cls(segments, policy=policy)

    @classmethod
    def from_trajectories(cls, points, times, *, traj_ids=None,
                          policy: ExecutionPolicy | None = None
                          ) -> "TrajectoryDB":
        """Build from per-trajectory polylines (see
        ``SegmentArray.from_trajectories``)."""
        segs = SegmentArray.from_trajectories(points, times, traj_ids)
        return cls(segs, policy=policy)

    @classmethod
    def from_scenario(cls, name: str, *, scale: float = 1.0, seed: int = 0,
                      policy: ExecutionPolicy | None = None) -> "TrajectoryDB":
        """Build one of the paper's §7.2 scenarios (S1–S10).

        The scenario's query workload is attached as ``db.scenario_queries``
        / ``db.scenario_d`` so examples and benchmarks need no second call.
        """
        from repro.data import trajgen
        segments, queries, d = trajgen.make_scenario(name, scale=scale,
                                                     seed=seed)
        db = cls(segments, policy=policy)
        db.scenario_queries = queries
        db.scenario_d = float(d)
        return db

    def __len__(self) -> int:
        return len(self.segments)

    # -- backends --------------------------------------------------------
    @staticmethod
    def _backend_key(name: str, pol: ExecutionPolicy) -> tuple:
        """The policy fields a backend's construction actually depends on —
        the adapter cache is keyed on these, so per-call policies with
        different knobs get (and reuse) their own adapters."""
        if name in ("pallas", "jnp"):
            return (pol.interpret, pol.cand_blk, pol.qry_blk, pol.capacity,
                    pol.compaction, pol.pipeline, pol.pruning,
                    pol.max_capacity_retries, pol.dispatch_ladder)
        if name == "shard":
            use_pallas = _shard_use_pallas(pol)
            # compaction (and kernel pruning) only matter on the Pallas
            # path — key on the effective values so policies differing in
            # an irrelevant knob share one (expensively constructed) mesh
            # engine.
            compaction = pol.compaction if use_pallas else "dense"
            # kernel-level pruning exists only on the fused Pallas path
            # (mirrors ShardedEngine.__init__'s normalization)
            pruning = (pol.pruning if use_pallas and compaction == "fused"
                       else "none")
            # pol.pruning itself (not just the kernel-effective value)
            # shapes construction too: hierarchical builds the pod-local
            # K-box plan index (PR 8)
            return (pol.shard_pods, pol.shard_capacity, use_pallas,
                    pol.shard_balance, pol.interpret, pol.cand_blk,
                    pol.qry_blk, compaction, pol.pipeline, pruning,
                    pol.pruning, pol.shard_sparse, pol.max_capacity_retries)
        if name == "rtree":
            return (pol.rtree_r, pol.rtree_fanout, pol.rtree_threads)
        return (pol.brute_chunk,)

    def backend(self, name: str,
                policy: ExecutionPolicy | None = None) -> QueryBackend:
        """The (cached) backend adapter for ``name`` under ``policy``
        (default: the database's construction policy)."""
        if name not in BACKENDS:
            raise ValueError(
                f"unknown backend {name!r}; choose from {BACKENDS}")
        pol = policy or self.policy
        key = (name,) + self._backend_key(name, pol)
        if key not in self._backends:
            if name in ("pallas", "jnp"):
                eng = copy.copy(self._base_engine)   # shares db/index/_packed
                eng.use_pallas = (name == "pallas")
                eng.interpret = resolve_interpret(pol.interpret)
                eng.cand_blk = pol.cand_blk
                eng.qry_blk = pol.qry_blk
                eng.default_capacity = pol.capacity
                eng.compaction = pol.compaction
                eng.pipeline = pol.pipeline
                eng.pruning = pol.pruning
                eng.max_capacity_retries = pol.max_capacity_retries
                eng.dispatch_ladder = check_ladder(pol.dispatch_ladder)
                self._backends[key] = EngineBackend(name, eng)
            elif name == "shard":
                from repro.core.distributed import ShardedEngine
                use_pallas = _shard_use_pallas(pol)
                compaction = pol.compaction if use_pallas else "dense"
                self._backends[key] = ShardBackend(ShardedEngine(
                    self.segments, pods=pol.shard_pods,
                    capacity_per_shard=pol.shard_capacity,
                    use_pallas=use_pallas, interpret=pol.interpret,
                    cand_blk=pol.cand_blk, qry_blk=pol.qry_blk,
                    compaction=compaction, pipeline=pol.pipeline,
                    balance=pol.shard_balance, pruning=pol.pruning,
                    index=self.index, sparse=pol.shard_sparse,
                    max_capacity_retries=pol.max_capacity_retries))
            elif name == "rtree":
                self._backends[key] = RTreeBackend(
                    RTreeEngine(self.segments, r=pol.rtree_r,
                                fanout=pol.rtree_fanout),
                    threads=pol.rtree_threads)
            else:  # brute
                self._backends[key] = BruteBackend(
                    self.segments, chunk=pol.brute_chunk)
        return self._backends[key]

    def engine(self, backend: str = "jnp",
               policy: ExecutionPolicy | None = None) -> DistanceThresholdEngine:
        """The underlying engine (perf-model interop: ``benchmark_host_curves``
        and friends still speak ``DistanceThresholdEngine``)."""
        be = self.backend(backend, policy)
        if not isinstance(be, EngineBackend):
            raise ValueError(f"backend {backend!r} has no engine")
        return be.engine

    # -- planning --------------------------------------------------------
    def planner(self, pol: ExecutionPolicy | None = None, *,
                num_queries: int = 0, backend: str = "jnp") -> QueryPlanner:
        """The :class:`~repro.core.planner.QueryPlanner` a policy resolves
        to — batching algorithm + params, capacity sizing (per-shard for
        ``backend="shard"``), spatial pruning and executor dispatch
        grouping.  A fitted §8 :class:`~repro.core.perfmodel.
        ResponseTimeModel` attached via :meth:`fit_response_model` feeds
        the planner's ``predict_hits`` (model-driven dispatch-group
        sizing replacing the constant hit-fraction default)."""
        pol = pol or self.policy
        if pol.pruning not in PRUNINGS:
            raise ValueError(f"unknown pruning {pol.pruning!r}; "
                             f"choose from {PRUNINGS}")
        capacity = pol.shard_capacity if backend == "shard" else pol.capacity
        predict_hits = (self.response_model.predict_batch_hits
                        if self.response_model is not None else None)
        pruning = pol.pruning
        index = self.index
        if backend == "shard" and pruning == "hierarchical":
            # Shard plans under hierarchical pruning address *pod-permuted*
            # segment positions: plan on the engine's pod-partitioned K-box
            # index (PR 8), whose box sub-ranges line up with both the pod
            # ownership slices and the engine's permuted packed copy.
            eng = self.backend("shard", pol).engine
            if eng.plan_index is not None:
                index = eng.plan_index
            else:
                pruning = eng.plan_pruning
        return QueryPlanner(
            index, algorithm=pol.batching,
            params=pol.resolved_batch_params(num_queries),
            default_capacity=capacity, group_size=pol.group_size,
            pruning=pruning, predict_hits=predict_hits,
            max_subranges=pol.max_subranges)

    def plan(self, queries: SegmentArray,
             policy: ExecutionPolicy | None = None, *,
             backend: str = "jnp", d: float | None = None) -> QueryPlan:
        """Build a refined query plan for *sorted-or-not* queries (sorts a
        copy if needed; the facade's query path reuses this).  Pass the
        query threshold ``d`` to get the pruned plan the query path would
        execute — without it planning is temporal-only."""
        qs, _ = self._sorted(queries)
        return self._make_plan(qs, policy or self.policy, backend, d=d)

    def _make_plan(self, sorted_queries: SegmentArray, pol: ExecutionPolicy,
                   backend: str = "jnp", d: float | None = None) -> QueryPlan:
        with spans.span("repro.plan"):
            return self.planner(pol, num_queries=len(sorted_queries),
                                backend=backend).plan(sorted_queries, d=d)

    @staticmethod
    def _sorted(queries: SegmentArray
                ) -> tuple[SegmentArray, np.ndarray | None]:
        """Sort queries by t_start, returning (sorted, permutation) where
        ``permutation[i]`` is the caller index of sorted position ``i``
        (None when already sorted)."""
        with spans.span("repro.facade.sort"):
            if queries.is_sorted():
                return queries, None
            order = np.argsort(queries.ts, kind="stable").astype(np.int64)
            return queries.take(order), order

    def _resolve_policy(self, batching: str | None,
                        policy: ExecutionPolicy | None,
                        batch_params: Mapping,
                        compaction: str | None = None,
                        pipeline: bool | None = None,
                        pruning: str | None = None) -> ExecutionPolicy:
        pol = policy or self.policy
        if batching is not None:
            pol = pol.with_(batching=batching, batch_params=None)
        if batch_params:
            pol = pol.with_(batch_params=dict(batch_params))
        if compaction is not None:
            pol = pol.with_(compaction=compaction)
        if pipeline is not None:
            pol = pol.with_(pipeline=pipeline)
        if pruning is not None:
            pol = pol.with_(pruning=pruning)
        return pol

    # -- the entrypoint --------------------------------------------------
    def query(self, queries: SegmentArray, d: float, *,
              backend: str = "jnp", batching: str | None = None,
              policy: ExecutionPolicy | None = None,
              compaction: str | None = None, pipeline: bool | None = None,
              pruning: str | None = None,
              **batch_params) -> QueryResult:
        """Find every (entry segment, query segment) pair within distance
        ``d`` during their temporal overlap.

        ``queries`` may be in any order — sorting happens internally and
        the returned ``QueryResult.query_idx`` is mapped back to the
        caller's order.  ``batching``/``**batch_params`` are shorthand for a
        one-off policy override (e.g. ``batching="periodic", s=48``), as are
        ``compaction=`` ("fused" in-kernel vs "dense" two-phase result
        compaction), ``pipeline=`` (async
        O(1)-sync executor vs per-batch sync loop) and ``pruning=``
        ("hierarchical" K-box sub-ranges + device-side live-tile dispatch,
        "spatial" bin-level candidate pruning, or "none" — all three give
        the same canonical result, in decreasing order of work avoided)
        for the engine backends (``"pallas"``/``"jnp"``/``"shard"``).

        Each call records its steps' seconds and counters
        (``repro.core.spans``) on ``QueryResult.stats``: ``span_seconds``
        and ``counts``.
        """
        with spans.recording(), spans.span("repro.query",
                                           segments=len(queries)):
            d = _validate_threshold(d)
            if len(queries) == 0:
                return QueryResult.from_result_set(
                    ResultSet.empty(), order=None, d=float(d),
                    backend=backend)
            _validate_segments(queries, "queries")
            pol = self._resolve_policy(batching, policy, batch_params,
                                       compaction, pipeline, pruning)
            be = self.backend(backend, pol)
            qs, order = self._sorted(queries)
            plan = (self._make_plan(qs, pol, backend, d=float(d))
                    if be.needs_plan else None)
            rs, stats = be.run(qs, float(d), plan)
            return QueryResult.from_result_set(
                rs, order=order, d=float(d), backend=backend,
                stats=stats, plan=plan)

    # -- streaming / serving ---------------------------------------------
    def query_stream(self, queries: SegmentArray, d: float, *,
                     backend: str = "jnp", batching: str | None = None,
                     policy: ExecutionPolicy | None = None,
                     compaction: str | None = None,
                     pipeline: bool | None = None,
                     pruning: str | None = None,
                     predict_seconds: Callable | None = None,
                     delay_hook: Callable | None = None,
                     **batch_params) -> tuple[QueryResult, SchedulerStats]:
        """Like :meth:`query`, but executes the plan through the
        deadline/re-issue scheduler (``repro.core.scheduler``) — the mode a
        serving deployment uses, where a straggling batch *group* is
        re-issued rather than stalling the response.

        Pipelined-stream semantics: the scheduler hands every worker call a
        *group* of consecutive batches (≥ 2 by default;
        ``ExecutionPolicy.stream_group_size`` overrides) and each call runs
        as one pipelined two-phase dispatch — ≤ 2 host syncs per group —
        so the O(1)-sync property amortizes inside the stream instead of
        collapsing to one sync per batch.  Re-issue, deduplication and
        deadlines (§8-model-derived, summed over the group) all operate on
        groups; see ``repro.core.scheduler``.

        Engine backends stream: ``'pallas'`` / ``'jnp'`` re-execute
        sub-plans on the single-device engine, and since PR 4 ``'shard'``
        routes every group through a per-pod routing layer
        (``repro.core.distributed.PodRouter``) over the temporal-pod mesh —
        ``SchedulerStats.routing`` then carries the per-pod fan-out and
        hit-balance accounting.
        """
        if backend not in ENGINE_BACKENDS:
            raise ValueError(
                f"query_stream requires an engine backend "
                f"{ENGINE_BACKENDS}, got {backend!r}")
        d = _validate_threshold(d)
        if len(queries) == 0:
            return (QueryResult.from_result_set(
                ResultSet.empty(), order=None, d=float(d), backend=backend),
                SchedulerStats())
        _validate_segments(queries, "queries")
        pol = self._resolve_policy(batching, policy, batch_params,
                                   compaction, pipeline, pruning)
        be = self.backend(backend, pol)
        if backend == "shard":
            from repro.core.distributed import PodRouter
            engine = PodRouter(be.engine)
        else:
            engine = be.engine
        qs, order = self._sorted(queries)
        plan = self._make_plan(qs, pol, backend, d=float(d))
        if predict_seconds is None and self.response_model is not None:
            predict_seconds = self.response_model.predict_batch_seconds
        sched = DeadlineScheduler(
            engine, workers=pol.stream_workers, slack=pol.stream_slack,
            min_deadline=pol.stream_min_deadline,
            predict_seconds=predict_seconds, delay_hook=delay_hook,
            group_size=pol.stream_group_size)
        rs, sstats = sched.execute(qs, float(d), plan)
        result = QueryResult.from_result_set(
            rs, order=order, d=float(d), backend=backend, plan=plan)
        return result, sstats


    # -- §8 response-time model ------------------------------------------
    def fit_response_model(self, queries: SegmentArray | None = None,
                           d: float | None = None, *, s: int = DEFAULT_BATCH_SIZE,
                           backend: str = "jnp", quick: bool = True,
                           num_epochs: int = 20, seed: int = 0):
        """Fit the §8 :class:`~repro.core.perfmodel.ResponseTimeModel` on
        this database and attach it as the default predictor.

        One model object then feeds the whole stack: the planner's
        ``predict_hits`` (model-driven dispatch-group sizing — replaces
        the constant ``AUTO_GROUP_HIT_FRACTION`` default), the broker's
        ``predict_seconds`` admission pricing, and ``query_stream``'s
        scheduler deadlines.  The α fit runs against the engine's
        configured pruning, so predictions track the *pruned* interaction
        workload.  ``quick=True`` (default) uses small benchmark grids —
        a couple of seconds on CPU; pass ``quick=False`` for the paper's
        full grids.  Returns the fitted model (also at
        ``self.response_model``; set that to ``None`` to detach).
        """
        from repro.core import perfmodel
        queries = queries if queries is not None else self.scenario_queries
        d = d if d is not None else self.scenario_d
        if queries is None or d is None:
            raise ValueError("fit_response_model needs a representative "
                             "query workload and threshold (or a scenario "
                             "database)")
        if quick:
            device = perfmodel.benchmark_device_curves(
                c_values=(256, 2048), q_values=(16, 128), repeats=1,
                seed=seed)
        else:
            device = perfmodel.benchmark_device_curves(seed=seed)
        engine = self.engine(backend)
        qs, _ = self._sorted(queries)
        host = perfmodel.benchmark_host_curves(
            engine, qs, s_values=(16, 64) if quick else (16, 32, 64, 128, 256),
            seed=seed)
        model = perfmodel.ResponseTimeModel(device, host,
                                            num_epochs=num_epochs)
        model.fit_alphas(engine, qs, float(d), s=s, seed=seed)
        self.response_model = model
        return model

    # -- session-oriented serving ----------------------------------------
    def broker(self, *, backend: str = "jnp",
               policy: ExecutionPolicy | None = None, **kwargs):
        """A :class:`repro.serve.broker.QueryBroker` bound to this database
        — the session-oriented serving front door: ``submit()`` returns a
        ticketed future-like handle, ``step()``/``run_until_idle()`` pump
        pending work one dispatch group at a time with incremental
        per-group result slices, admission control prices tickets with the
        §8 perf model, and ``backend="shard"`` fans groups out per pod.
        Keyword arguments are forwarded to the broker constructor
        (``predict_seconds=``, ``max_inflight_interactions=``, ...).
        """
        from repro.serve.broker import QueryBroker
        return QueryBroker(self, backend=backend, policy=policy, **kwargs)


def __getattr__(name: str):
    # Broker types are re-exported here (the facade is the stable surface)
    # but defined in repro.serve.broker, which imports this module — the
    # lazy hook breaks the cycle.
    if name in ("QueryBroker", "QueryTicket", "GroupSlice",
                "AdmissionError", "DeadlineExceededError",
                "TicketHealth", "Degradation"):
        from repro.serve import broker as _broker
        return getattr(_broker, name)
    if name == "RetryPolicy":
        from repro.serve.retry import RetryPolicy
        return RetryPolicy
    if name == "FaultPlan":
        from repro.faults import FaultPlan
        return FaultPlan
    if name == "FaultSpec":
        from repro.faults import FaultSpec
        return FaultSpec
    raise AttributeError(f"module 'repro.api' has no attribute {name!r}")


__all__ = [
    "BACKENDS", "DEFAULT_BATCH_SIZE", "ENGINE_BACKENDS", "ExecutionPolicy",
    "QueryBackend", "QueryResult", "TrajectoryDB", "EngineBackend",
    "RTreeBackend", "BruteBackend", "ShardBackend", "QueryBroker",
    "QueryTicket", "GroupSlice", "AdmissionError", "DeadlineExceededError",
    "CapacityError", "PodFailedError", "RetryPolicy", "TicketHealth",
    "Degradation", "FaultPlan", "FaultSpec",
]
