"""Distributed distance-threshold query execution (multi-chip / multi-pod).

The paper notes (§1) that "a spatiotemporal database can be easily
partitioned (e.g., temporally) and queried across multiple compute nodes".
This module implements that story on a JAX mesh:

* **pod axis — temporal partition.**  :func:`temporal_pod_partition` splits
  the sorted segment array into per-pod contiguous time slices plus a halo
  (segments whose temporal extent crosses the boundary), so every pod can
  answer queries over its slice independently and results concatenate.
* **data axis — candidate sharding.**  The contiguous candidate range of a
  batch is block-sharded on segment index; each device runs the interaction
  kernel on (local candidates × replicated queries).  Per-device results
  compact locally; hit counts ``psum``-reduce for result sizing.  This is
  the paper's "one thread per candidate" scaled up a level: one *device*
  per candidate shard.
* **model axis — query sharding.**  For batches with many queries and few
  candidates the engine shards queries instead (beyond-paper: the paper
  always parallelizes over candidates).  :func:`choose_sharding` picks by
  aspect ratio.

All functions build ``shard_map``-wrapped jitted callables bound to a mesh;
the dry-run lowers them on the production meshes.

PR 3 promotes this module from "mesh machinery" to a first-class backend:
:class:`ShardedEngine` implements the ``repro.core.executor``
``BatchDispatcher`` protocol over a temporal-pod mesh, so the generic
pipelined executor gives the sharded path the same ≤ 2-host-syncs-per-
query-set property as the single-device engine — hit counts ``psum``-reduce
to one global total on device, per-pod results come back globally indexed,
and duplicate pairs are impossible because pods *own* disjoint
``t_start`` ranges (see :func:`temporal_pod_partition`).  The facade
registers it as ``backend="shard"`` (``repro.api``).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import faults
from repro.core.executor import Dispatch, ResultSet, make_executor
from repro.core.planner import as_query_plan, bucket_capacity
from repro.core.segments import SegmentArray
from repro.kernels import ops, ref
from repro.kernels.distthresh import resolve_interpret


# ----------------------------------------------------------------------
# temporal pod partition (paper's multi-node suggestion)
# ----------------------------------------------------------------------
#: Accepted ``temporal_pod_partition(balance=...)`` strategies.
POD_BALANCES = ("time", "num_ints")


def temporal_pod_partition(db: SegmentArray, num_pods: int, *,
                           halo: bool = False,
                           balance: str = "time") -> list[tuple[int, int]]:
    """Per-pod inclusive ``[first, last]`` slices of the sorted database.

    With ``halo=False`` (the default) the slices are an exact *partition*:
    pod ``p`` **owns** a contiguous run of the t_start-sorted segments,
    every segment is owned by exactly one pod, and empty pods come back as
    valid empty ranges ``(first, first - 1)``.  This ownership is what
    makes cross-pod result sets trivially duplicate-free: an interaction
    pair is evaluated by the unique owner of its entry segment (the sharded
    backend's "halo dedup" is by construction, not by filtering).

    ``balance`` picks where the ownership boundaries go:

    * ``"time"`` (the default, unchanged): pod ``p`` owns the segments
      whose ``t_start`` falls in the p-th *equal-width* slice of the
      temporal extent.  Temporally dense regions make their pod own (and
      evaluate) disproportionately many candidate rows.
    * ``"num_ints"``: boundaries are placed at equal quantiles of the
      per-segment candidate-load prefix sum — the same prefix-sum
      machinery the batching algorithms use for their ``numInts``
      accounting, applied to pods.  A segment's expected interaction load
      under a stationary query stream is proportional to how many queries
      temporally overlap it, i.e. to ``duration(e) + mean query
      duration`` (interval-overlap probability); lacking the workload at
      partition time, the database's own duration distribution stands in
      for the queries'.  Equalizing that cumulative weight equalizes
      expected per-pod interactions on a temporally skewed database (the
      total candidate-row count is partition-invariant; only its per-pod
      distribution moves).

    With ``halo=True`` each slice is additionally *widened* to start at the
    first segment whose running-max ``t_end`` reaches the pod's window
    start — segments with an earlier ``t_start`` that extend into the
    window.  Halo slices overlap (a replica placement/routing view, not an
    ownership view); consumers that evaluate over halo slices must dedup by
    entry ownership.

    Degenerate inputs return valid (possibly empty) slices instead of
    nonsense ranges: an empty database yields ``num_pods`` empty slices,
    and ``num_pods`` larger than the number of distinct time slices (or
    segments) leaves the surplus pods empty.
    """
    if num_pods <= 0:
        raise ValueError(f"num_pods must be positive, got {num_pods}")
    if balance not in POD_BALANCES:
        raise ValueError(f"unknown balance {balance!r}; "
                         f"choose from {POD_BALANCES}")
    n = len(db)
    if n == 0:
        return [(0, -1)] * num_pods
    if not db.is_sorted():
        raise ValueError("database must be sorted by t_start")
    if balance == "time":
        edges = np.linspace(float(db.ts[0]), float(db.ts[-1]), num_pods + 1)
        # Ownership boundaries: bounds[p] is the first segment of pod p.
        # With fewer distinct t_start values than pods (e.g. all segments
        # at one instant) interior edges collapse and the surplus pods are
        # empty.
        bounds = np.concatenate([
            [0], np.searchsorted(db.ts, edges[1:-1], side="left"), [n]
        ]).astype(np.int64)
    else:
        # Equal-load boundaries via the prefix sum of per-segment candidate
        # weight — expected overlapping-query count ∝ own duration + mean
        # duration (the db's durations proxy the workload's): pod p starts
        # at the first index whose cumulative weight exceeds p/num_pods of
        # the total.
        dur = np.maximum(db.te.astype(np.float64)
                         - db.ts.astype(np.float64), 0.0)
        cum_w = np.cumsum(dur + max(float(dur.mean()), 1e-30))
        targets = cum_w[-1] * np.arange(1, num_pods) / num_pods
        interior = np.searchsorted(cum_w, targets, side="left") + 1
        bounds = np.concatenate([[0], interior, [n]]).astype(np.int64)
    out = []
    if halo:
        te_running_max = np.maximum.accumulate(db.te.astype(np.float64))
    for p in range(num_pods):
        first, last = int(bounds[p]), int(bounds[p + 1]) - 1
        if halo and last >= first:
            # Widen to the first segment whose running-max t_end reaches
            # the pod's window start: every earlier-starting segment that
            # extends into the window is included.
            win0 = (edges[p] if balance == "time" else float(db.ts[first]))
            first = int(np.searchsorted(te_running_max, win0, side="left"))
        out.append((first, max(last, first - 1)))
    return out


def route_query_to_pods(qt0: float, qt1: float, db: SegmentArray,
                        pod_slices: list[tuple[int, int]]) -> list[int]:
    """Pods whose temporal window may hold candidates for [qt0, qt1].

    Degenerate inputs are routed nowhere: an empty database (or all-empty
    pod slices) returns ``[]``, and an empty query extent (``qt1 < qt0``)
    matches no pod.
    """
    if len(db) == 0 or qt1 < qt0:
        return []
    pods = []
    for p, (first, last) in enumerate(pod_slices):
        if last < first:
            continue
        # pod's segments can extend past its window end; use actual extents
        seg_lo = float(db.ts[first])
        seg_hi = float(db.te[first:last + 1].max())
        if seg_lo <= qt1 and seg_hi >= qt0:
            pods.append(p)
    return pods


# ----------------------------------------------------------------------
# sharded device computations
# ----------------------------------------------------------------------
def choose_sharding(num_candidates: int, num_queries: int,
                    cand_ways: int, qry_ways: int) -> str:
    """Pick candidate- vs query-sharding by shard aspect ratio.

    Candidate-sharding leaves ``C/cand_ways`` rows per device; if that is
    smaller than the tile (wasted compute in padding) while Q is large, the
    query-sharded layout wastes less.  The paper always candidate-shards;
    this switch is a beyond-paper optimization evaluated in §Perf.
    """
    c_per = num_candidates / max(cand_ways, 1)
    q_per = num_queries / max(qry_ways, 1)
    return "candidates" if c_per >= q_per else "queries"


def make_sharded_count_fn(mesh: Mesh, cand_axes: Sequence[str],
                          qry_axes: Sequence[str] = (), *,
                          use_pallas: bool = False,
                          interpret: bool | None = None):
    """Jitted global-count function: entries sharded on dim 0 over
    ``cand_axes``, queries sharded over ``qry_axes`` (replicated if empty).

    Returns ``fn(entries (C,8), queries (Q,8), d) -> int32 scalar`` with the
    full-mesh psum built in.  C and Q must divide by the respective axis
    sizes (the host engine pads with non-hitting rows).
    """
    cand_axes = tuple(cand_axes)
    qry_axes = tuple(qry_axes)
    all_axes = cand_axes + qry_axes

    def local(entries, queries, d):
        _, _, hit = ref.interaction_tile(entries, queries, d)
        cnt = jnp.sum(hit.astype(jnp.int32))
        return jax.lax.psum(cnt, all_axes) if all_axes else cnt

    shmapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(cand_axes if cand_axes else None, None),
                  P(qry_axes if qry_axes else None, None), P()),
        out_specs=P(),
    )
    return jax.jit(shmapped)


def make_sharded_query_fn(mesh: Mesh, cand_axes: Sequence[str],
                          capacity_per_shard: int, *,
                          qry_axes: Sequence[str] = (),
                          use_pallas: bool = False,
                          interpret: bool | None = None,
                          cand_blk: int = 256, qry_blk: int = 256):
    """Jitted full query step with local compaction, sharded in 2-D.

    Candidates shard over ``cand_axes`` (the paper's parallelization) and —
    beyond-paper — queries optionally shard over ``qry_axes``, so a batch
    uses the *whole* mesh instead of leaving the model axis idle: per-device
    interactions drop by ``prod(qry_axes)``×.  ``fn(entries (C,8), queries
    (Q,8), d)`` returns result buffers whose leading dim is
    ``num_shards × capacity_per_shard``, with ``entry_idx``/``query_idx``
    globalized via shard offsets, plus per-shard counts (overflow
    detection) — the multi-chip analogue of Algorithm 1's atomic result
    append, without atomics.
    """
    cand_axes = tuple(cand_axes)
    qry_axes = tuple(qry_axes)
    ways = int(np.prod([mesh.shape[a] for a in cand_axes]))
    all_axes = cand_axes + qry_axes

    def _axis_offset(axes, local_dim):
        idx = jnp.zeros((), jnp.int32)
        mult = 1
        for a in reversed(axes):
            idx = idx + jax.lax.axis_index(a) * mult
            mult *= mesh.shape[a]
        return idx * local_dim

    def local(entries, queries, d):
        out = ops.query_block(
            entries, queries, d, capacity=capacity_per_shard,
            use_pallas=use_pallas, interpret=interpret,
            cand_blk=cand_blk, qry_blk=qry_blk)
        # tile-prune diagnostics are not part of this legacy driver's
        # contract (its out_specs predate them)
        out = {k: v for k, v in out.items()
               if k not in ("pruned_tiles", "num_tiles")}
        valid = out["entry_idx"] >= 0
        e_off = _axis_offset(cand_axes, entries.shape[0])
        out["entry_idx"] = jnp.where(valid, out["entry_idx"] + e_off, -1)
        if qry_axes:
            q_off = _axis_offset(qry_axes, queries.shape[0])
            out["query_idx"] = jnp.where(valid, out["query_idx"] + q_off, -1)
        out["count"] = out["count"][None]
        return out

    shmapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(cand_axes, None),
                  P(qry_axes if qry_axes else None, None), P()),
        out_specs={"entry_idx": P(all_axes), "query_idx": P(all_axes),
                   "t_enter": P(all_axes), "t_exit": P(all_axes),
                   "count": P(all_axes)},
        check_vma=False,     # as in make_pod_query_fn: Pallas may run here
    )
    return jax.jit(shmapped), ways


def make_pod_query_fn(mesh: Mesh, capacity_per_shard: int, *,
                      pod_axis: str = "pod", use_pallas: bool = False,
                      interpret: bool | None = None, cand_blk: int = 256,
                      qry_blk: int = 256, compaction: str = "dense",
                      pruning: str = "none", sparse: bool = False):
    """Jitted per-batch query step for the temporal-pod mesh backend.

    ``fn(entries (P, C_loc, 8), offsets (P,), [lens (P,),] queries (Q, 8),
    d)`` runs ``ops.query_block`` on every pod's local candidate block
    against the replicated query batch and returns result buffers whose
    leading dim is ``P × capacity_per_shard``:

    * ``entry_idx`` is **globalized on device** via the per-pod ``offsets``
      (the pod's first owned global segment index) — the host never remaps;
    * ``count`` is the per-pod hit count vector (overflow detection);
    * ``total`` is the ``psum``-reduced global hit count — one scalar the
      executor reads for exact result sizing, the multi-device analogue of
      the single-device kernel's exact-count contract.

    ``pruning`` is forwarded to ``ops.query_block`` *inside* the
    ``shard_map`` body, where everything is traced: ``"spatial"`` derives
    the per-tile MBRs in-graph (PR 5) and ``"hierarchical"`` (PR 7) makes
    **each pod build its own live-tile list in-graph** from its resident
    shard (``ops._jit_live_tiles``) and dispatch the scalar-prefetched
    live-tile kernel — dead slots sort to the tail and cost one scalar
    compare per slot, with no host round-trip and no cross-pod traffic.

    ``sparse`` (PR 8) adds the per-pod candidate-length vector ``lens``
    and short-circuits pods with zero candidates for the batch: the whole
    ``query_block`` body sits under a ``lax.cond`` whose false branch
    emits an empty result block, so a non-routed pod runs one predicate
    instead of a full padded kernel launch — the mesh-level analogue of
    the kernel's ``@pl.when`` tile early-out.  SPMD stays sound because
    shapes are identical on both branches, a skipped pod contributes an
    exact zero to the hit count, and the ``psum`` runs **outside** the
    cond (a collective inside a divergent branch would deadlock the
    mesh).  Results are bit-identical to the dense step.

    Capacity (and the block/compaction knobs) are baked into the returned
    callable; the sharded engine keeps one per retry capacity.
    """

    def _step(entries, offsets, queries, d):
        out = ops.query_block(
            entries[0], queries, d, capacity=capacity_per_shard,
            use_pallas=use_pallas, interpret=interpret,
            cand_blk=cand_blk, qry_blk=qry_blk, compaction=compaction,
            pruning=pruning)
        valid = out["entry_idx"] >= 0
        out["entry_idx"] = jnp.where(valid, out["entry_idx"] + offsets[0], -1)
        return out

    def _finish(out):
        cnt = out["count"]
        return {
            "entry_idx": out["entry_idx"],
            "query_idx": out["query_idx"],
            "t_enter": out["t_enter"],
            "t_exit": out["t_exit"],
            "count": cnt[None],
            "total": jax.lax.psum(cnt, pod_axis),
            "pruned_tiles": out["pruned_tiles"][None],
            "num_tiles": out["num_tiles"][None],
        }

    if sparse:
        def local(entries, offsets, lens, queries, d):
            out = jax.lax.cond(
                lens[0] > 0,
                lambda: _step(entries, offsets, queries, d),
                lambda: ops._empty_block(capacity_per_shard,
                                         entries.dtype))
            # psum after the cond: every pod participates, skipped pods
            # contribute their (exact) zero count.
            return _finish(out)
        in_specs = (P(pod_axis, None, None), P(pod_axis), P(pod_axis),
                    P(None, None), P())
    else:
        def local(entries, offsets, queries, d):
            return _finish(_step(entries, offsets, queries, d))
        in_specs = (P(pod_axis, None, None), P(pod_axis), P(None, None),
                    P())

    # check_vma=False: shard_map's varying-axes check refuses a
    # pallas_call, whose out_shape names no varying axes, and would also
    # refuse the cond, whose branches vary over the pods in different
    # outputs (the constant empty block against the per-pod kernel).
    # Every output is per pod except ``total``, which the psum replicates.
    shmapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs={"entry_idx": P(pod_axis), "query_idx": P(pod_axis),
                   "t_enter": P(pod_axis), "t_exit": P(pod_axis),
                   "count": P(pod_axis), "total": P(),
                   "pruned_tiles": P(pod_axis), "num_tiles": P(pod_axis)},
        check_vma=False,
    )
    return jax.jit(shmapped)


class _PodShardDispatcher:
    """``BatchDispatcher`` over a temporal-pod mesh (executor protocol).

    ``dispatch`` slices each pod's intersection with the batch's contiguous
    candidate range out of the packed database, pads every pod's block to a
    shared bucketed width (pad rows use a temporal extent beyond the data
    — and a *different* instant than query padding, so pad×pad pairs can
    never hit), and queues one ``shard_map`` step — no host reads, so the
    pipelined executor's phase A stays fully asynchronous.
    """

    def __init__(self, engine: "ShardedEngine", q_packed: np.ndarray,
                 d: float):
        self.engine = engine
        self.q_packed = q_packed
        self.d = d
        # Pad instants beyond the database AND this query set — a query
        # extending past the database's extent must not overlap entry pad
        # rows — with query pads at an instant of their own.
        self._pad_e, self._pad_q = ops.pad_instants(engine._t_end, q_packed)

    def _pod_lens(self, batch) -> tuple[list[int], list[int]]:
        """Per-pod (first index, length) of the batch's candidate range
        intersected with each pod's ownership slice — the exact fan-out."""
        los, lens = [], []
        for pf, plast in self.engine.pod_slices:
            lo = max(batch.cand_first, pf)
            hi = min(batch.cand_last, plast)
            los.append(lo)
            lens.append(max(hi - lo + 1, 0))
        return los, lens

    def dispatch(self, batch, capacity: int):
        se = self.engine
        los, lens = self._pod_lens(batch)
        if faults.armed():
            faults.inject("shard.dispatch", q_first=int(batch.q_first))
            # Pod-dropout target: one consultation per *live* pod of this
            # dispatch, so a plan can drop exactly the pod(s) it names
            # (``match={"pod": k}``) and only when they hold real work.
            for p, n in enumerate(lens):
                if n:
                    faults.inject("shard.pod", pod=p,
                                  q_first=int(batch.q_first))
        c_loc = bucket_capacity(max(max(lens), 1), se.cand_blk)
        # Pod-local candidate blocks, padded with rows at _pad_e (never
        # overlaps real data, real queries, or query padding at _pad_q).
        # Under a hierarchical plan the batch ranges are permuted
        # positions, so slice the permuted packed copy — pod ownership
        # intervals are identical in permuted coordinates (the pod-local
        # perm reorders only within bin ∩ pod pieces).
        src = (se._packed_perm if se.plan_pruning == "hierarchical"
               else se._packed)
        stacked = np.stack([ops.pad_block(src[lo:lo + n], c_loc, self._pad_e)
                            for lo, n in zip(los, lens)])
        offsets = np.asarray(los, np.int32)
        # Replicated query batch, bucketed on the same ladder as the
        # candidate blocks so the jit cache stays O(log²).
        qs = self.q_packed[batch.q_first:batch.q_last + 1]
        qs = ops.pad_block(qs, bucket_capacity(qs.shape[0], se.qry_blk),
                           self._pad_q)
        lens_arr = np.asarray(lens, np.int32)
        return self._launch(batch, capacity, (stacked, offsets, lens_arr, qs))

    def _launch(self, batch, capacity: int, prepared) -> Dispatch:
        stacked, offsets, lens, qs = prepared
        fn = self.engine._fn(capacity)
        if self.engine.sparse:
            out = fn(jnp.asarray(stacked), jnp.asarray(offsets),
                     jnp.asarray(lens), jnp.asarray(qs), np.float32(self.d))
        else:
            out = fn(jnp.asarray(stacked), jnp.asarray(offsets),
                     jnp.asarray(qs), np.float32(self.d))
        return Dispatch(batch, capacity, out, ctx=prepared)

    def redispatch(self, dp: Dispatch, capacity: int) -> Dispatch:
        """Overflow retry: only the capacity changed, so reuse the prepared
        per-pod blocks / padded queries carried in ``dp.ctx``."""
        return self._launch(dp.batch, capacity, dp.ctx)

    def count(self, dp) -> int:
        count = int(dp.out["total"])
        if faults.armed():
            count = faults.corrupt("shard.count", count,
                                   q_first=int(dp.batch.q_first))
        return count

    def tile_stats(self, dp) -> tuple[int, int]:
        """Kernel-level pruning counters summed over the pods (executor
        hook; see ``repro.core.executor._tile_stats``)."""
        return (int(np.asarray(dp.out["pruned_tiles"]).sum()),
                int(np.asarray(dp.out["num_tiles"]).sum()))

    def retry_capacity(self, dp) -> int | None:
        per_shard = int(np.asarray(dp.out["count"]).max())
        return (bucket_capacity(per_shard)
                if per_shard > dp.capacity else None)

    def marshal(self, dp, count: int):
        if faults.armed():
            faults.inject("shard.marshal", q_first=int(dp.batch.q_first))
        db = self.engine.db
        ent = np.asarray(dp.out["entry_idx"])
        # Mask on the -1 pads rather than trusting ``count`` (the psum
        # total may be corrupted by a chaos plan); no valid rows = no part.
        keep = ent >= 0
        if not keep.any():
            return None
        e_global = ent[keep].astype(np.int64)
        if self.engine.plan_pruning == "hierarchical":
            # device rows sit at permuted positions; map back so the
            # caller-visible entry_idx never changes (same contract as the
            # single-device hierarchical path)
            perm = self.engine._perm
            if perm is not None:
                e_global = perm[e_global]
        q_local = np.asarray(dp.out["query_idx"])[keep].astype(np.int64)
        return ResultSet(
            entry_idx=e_global,
            entry_traj=db.traj_id[e_global].astype(np.int64),
            entry_seg=db.seg_id[e_global].astype(np.int64),
            query_idx=dp.batch.q_first + q_local,
            t_enter=np.asarray(dp.out["t_enter"])[keep],
            t_exit=np.asarray(dp.out["t_exit"])[keep],
        )


class ShardedEngine:
    """First-class sharded query backend over a temporal-pod mesh.

    The multi-device sibling of ``repro.core.engine.
    DistanceThresholdEngine``: the database is temporally partitioned
    across the mesh's ``pod`` axis once (:func:`temporal_pod_partition`,
    ownership slices — duplicate pairs are impossible by construction), and
    each batch's contiguous candidate range is answered by the pods owning
    its sub-ranges against the replicated query batch.  Execution runs
    through the shared ``repro.core.executor`` drivers, so the pipelined
    path keeps ≤ 2 host syncs per dispatch group (``ExecStats.num_syncs``
    — one group per query set unless the §8-model derivation splits a
    high-hit-volume plan) with ``psum``-reduced exact hit counts and the
    same bucketed overflow-retry protocol as the single-device engine.

    Registered through the facade as ``backend="shard"``
    (``repro.api.TrajectoryDB.query``); constructed there from
    ``ExecutionPolicy.shard_pods`` / ``shard_capacity``.

    ``pruning="hierarchical"`` (PR 8) rebuilds the PR 7 K-box index
    **per pod** over each pod's ownership slice
    (:meth:`repro.core.index.PodPartitionedIndex.build_partitioned`,
    from the base ``index=`` the facade passes in): the pod-local
    permutation reorders segments only within bin ∩ pod pieces, so pod
    ownership intervals and bin ranges survive unchanged and the
    planner prunes shard plans at *box* granularity — the single-device
    planner-level win, on the mesh.  Result ``entry_idx`` maps back
    through the composed ``perm``, so caller-visible results are
    byte-identical to every other backend × pruning mode.  The
    kernel-level win rides along on the fused Pallas path
    (``shard_use_pallas=True``): ``make_pod_query_fn`` builds the
    compacted live-tile lists *in-graph* per pod (stable
    ``jnp.argsort`` over the tile box test — shard_map tracers, so no
    host-side ``np.nonzero``).

    ``sparse=True`` (PR 8, default) makes dispatch skip pods whose
    candidate intersection with a batch is empty: the per-pod length
    vector rides into the sharded step and zero-row pods short-circuit
    under ``lax.cond`` (see :func:`make_pod_query_fn`) instead of
    executing full padded blocks, with ``psum`` totals exact by zero
    contribution.  :class:`RoutingStats` reports the avoided work.
    """

    def __init__(self, db: SegmentArray, *, mesh: Mesh | None = None,
                 pods: int | None = None, capacity_per_shard: int = 4096,
                 use_pallas: bool = False, interpret: bool | None = None,
                 cand_blk: int = 256, qry_blk: int = 256,
                 compaction: str = "dense", pipeline: bool = True,
                 balance: str = "time", pruning: str = "spatial",
                 index=None, sparse: bool = True,
                 max_capacity_retries: int = 3):
        self.db = db if db.is_sorted() else db.sort_by_tstart()
        self._packed = self.db.packed()
        if mesh is None:
            devices = jax.devices()
            if pods is not None:
                devices = devices[:max(min(pods, len(devices)), 1)]
            mesh = Mesh(np.asarray(devices), ("pod",))
        self.mesh = mesh
        self.pod_axis = mesh.axis_names[0]
        self.ways = int(mesh.shape[self.pod_axis])
        self.balance = balance
        self.pod_slices = temporal_pod_partition(self.db, self.ways,
                                                 balance=balance)
        self.capacity_per_shard = capacity_per_shard
        self.use_pallas = use_pallas
        # Interpret mode follows the platform of the mesh's devices.
        self.interpret = resolve_interpret(interpret, mesh.devices.flat[0])
        self.cand_blk = cand_blk
        self.qry_blk = qry_blk
        self.compaction = compaction
        self.pipeline = pipeline
        self.sparse = bool(sparse)
        self.max_capacity_retries = int(max_capacity_retries)
        # Planner-level pruning: hierarchical needs the pod-local K-box
        # rebuild (from the facade's base index); without one, shard
        # plans can only use bin-granular (spatial) ranges.
        self.plan_pruning = pruning
        self.plan_index = None
        self._perm = None
        self._packed_perm = self._packed
        if pruning == "hierarchical":
            if index is None:
                self.plan_pruning = "spatial"
            else:
                from repro.core.index import PodPartitionedIndex
                self.plan_index = PodPartitionedIndex.build_partitioned(
                    index, self.db, self.pod_slices)
                self._perm = self.plan_index.perm
                self._packed_perm = self._packed[self._perm]
        # Kernel-level tile pruning only exists on the fused Pallas path;
        # normalizing here keeps the jit-cache key honest.
        self.pruning = (pruning if use_pallas and compaction == "fused"
                        else "none")
        self._t_end = float(self.db.temporal_extent[1])
        self._fns: dict[int, object] = {}

    # ------------------------------------------------------------------
    def _fn(self, capacity: int):
        """The jitted sharded step for one (bucketed) capacity."""
        if capacity not in self._fns:
            self._fns[capacity] = make_pod_query_fn(
                self.mesh, capacity, pod_axis=self.pod_axis,
                use_pallas=self.use_pallas, interpret=self.interpret,
                cand_blk=self.cand_blk, qry_blk=self.qry_blk,
                compaction=self.compaction, pruning=self.pruning,
                sparse=self.sparse)
        return self._fns[capacity]

    def dispatcher(self, queries_packed: np.ndarray,
                   d: float) -> _PodShardDispatcher:
        return _PodShardDispatcher(self, queries_packed, float(d))

    # ------------------------------------------------------------------
    def execute(self, queries: SegmentArray, d: float, plan,
                *, pipeline: bool | None = None, on_group=None,
                dispatcher=None):
        """Run a plan on the mesh — same contract as the single-device
        ``DistanceThresholdEngine.execute`` (``plan`` may be a ``BatchPlan``
        or a refined ``QueryPlan``; per-batch capacities are *per shard*;
        ``on_group`` is the executor's group-completion hook).
        ``dispatcher`` substitutes a pre-built pod dispatcher — the seam
        :class:`PodRouter` uses to thread routing accounting through."""
        if not queries.is_sorted():
            raise ValueError(
                "queries must be sorted by t_start; use "
                "repro.api.TrajectoryDB.query, which sorts automatically")
        qplan = as_query_plan(plan,
                              default_capacity=self.capacity_per_shard)
        use_pipeline = self.pipeline if pipeline is None else pipeline
        if dispatcher is None:
            dispatcher = self.dispatcher(queries.packed(), d)
        executor = make_executor(dispatcher, pipeline=use_pipeline,
                                 on_group=on_group,
                                 max_capacity_retries=getattr(
                                     self, "max_capacity_retries", 3))
        return executor.run(qplan)


@dataclasses.dataclass(eq=False)      # identity compare: ndarray + lock fields
class RoutingStats:
    """Per-pod routing accounting for one :class:`PodRouter` binding.

    ``pods_per_batch[k]`` is how many pods hold a non-empty intersection
    of the k-th *dispatched* batch's candidate range with their ownership
    slice — the SPMD step still runs on the whole mesh, but the non-routed
    pods' candidate blocks are empty padding, so this is the exact fan-out
    (the dispatch-time refinement of :func:`route_query_to_pods`' temporal
    routing view).
    ``pod_hits`` accumulates marshalled hit rows per pod — the load signal
    the ``balance="num_ints"`` partition is meant to even out.

    Both count **work dispatched to the pods**, not unique results: on the
    deadline-scheduler path a straggling group that gets re-issued is
    accounted once per execution (its duplicate *results* are dropped by
    the scheduler, but each execution did load the pods).  On the broker's
    single-threaded pump (no re-issue) ``pod_hits.sum()`` equals the
    ticket's result rows exactly.  Updates are lock-protected — scheduler
    worker threads share one stats object.
    """

    num_pods: int = 0
    batches: int = 0
    pods_per_batch: list = dataclasses.field(default_factory=list)
    pod_hits: np.ndarray | None = None
    #: Pod executions avoided by sparse dispatch (PR 8): a pod counted
    #: here had zero candidates for its batch and short-circuited under
    #: the sharded step's ``lax.cond`` instead of running padding.
    pods_skipped: int = 0
    #: Padded entry×query interaction slots those skipped executions
    #: would have evaluated (``skipped × C_loc × Q_pad`` per batch).
    padded_interactions_avoided: int = 0
    _lock: object = dataclasses.field(default_factory=threading.Lock,
                                      repr=False, compare=False)

    @property
    def mean_pods_per_batch(self) -> float:
        return (float(np.mean(self.pods_per_batch))
                if self.pods_per_batch else 0.0)

    @property
    def hit_balance(self) -> float:
        """max/mean per-pod hit load (1.0 = perfectly even; 0 if no hits).

        Zero-routed workloads (every batch fully pruned, or no pods at
        all) report 0.0 rather than dividing by a zero mean.
        """
        if self.pod_hits is None or self.pod_hits.size == 0:
            return 0.0
        if int(self.pod_hits.sum()) == 0:
            return 0.0
        return float(self.pod_hits.max() / self.pod_hits.mean())


class _RoutedPodDispatcher(_PodShardDispatcher):
    """The pod dispatcher with per-batch fan-out accounting (non-empty
    pod candidate intersections) and per-pod hit accounting on marshal —
    what :class:`PodRouter` hands the executors."""

    def __init__(self, router: "PodRouter", q_packed: np.ndarray, d: float):
        super().__init__(router.engine, q_packed, d)
        self.router = router

    def dispatch(self, batch, capacity: int):
        _, lens = self._pod_lens(batch)
        live = sum(1 for n in lens if n > 0)
        dp = super().dispatch(batch, capacity)
        st = self.router.stats
        with st._lock:
            st.batches += 1
            st.pods_per_batch.append(live)
            if self.engine.sparse:
                skipped = self.engine.ways - live
                st.pods_skipped += skipped
                # prepared ctx = (stacked (P, C_loc, 8), offsets, lens,
                # qs (Q_pad, 8)): each skipped pod would have evaluated
                # the full padded C_loc × Q_pad block
                st.padded_interactions_avoided += (
                    skipped * dp.ctx[0].shape[1] * dp.ctx[3].shape[0])
        return dp

    def record_empty(self, batch) -> None:
        """Executor hook: a zero-candidate batch was skipped host-side.
        Record an explicit empty routing row (0 pods touched) so the
        stats ledger covers every planned batch instead of silently
        undercounting fully-pruned groups."""
        st = self.router.stats
        with st._lock:
            st.batches += 1
            st.pods_per_batch.append(0)

    def marshal(self, dp, count: int):
        st = self.router.stats
        per_pod = np.minimum(np.asarray(dp.out["count"], np.int64),
                             dp.capacity)
        with st._lock:
            st.pod_hits += per_pod
        return super().marshal(dp, count)


class PodRouter:
    """Per-pod shard routing layer over a :class:`ShardedEngine` — the
    serving-side face of the mesh backend.

    The broker (``repro.serve.broker.QueryBroker``) and the deadline
    scheduler hand this object a ticket's batch *groups*; each group fans
    out to the per-pod candidate slices through one pipelined ``shard_map``
    dispatch (``_RoutedPodDispatcher``), per-pod hits merge into one
    globally indexed ``ResultSet`` (``psum``-reduced exact counts, ≤ 2 host
    syncs per group), and :class:`RoutingStats` records how many pods each
    batch actually needed (non-empty candidate intersections) and how the
    hit load balanced across pods.

    ``execute`` has the same contract as the engines', so a
    ``DeadlineScheduler`` can drive a router directly — this is what closed
    the ROADMAP's "``query_stream`` never reaches the ``ShardedEngine``
    pods" gap (``repro.api.TrajectoryDB.query_stream(backend="shard")``).
    """

    def __init__(self, engine: ShardedEngine):
        self.engine = engine
        self.stats = RoutingStats(
            num_pods=engine.ways,
            pod_hits=np.zeros(engine.ways, np.int64))

    @property
    def default_capacity(self) -> int:
        """Per-shard capacity (scheduler/executor interop)."""
        return self.engine.capacity_per_shard

    def dispatcher(self, queries_packed: np.ndarray,
                   d: float) -> _RoutedPodDispatcher:
        return _RoutedPodDispatcher(self, queries_packed, float(d))

    def execute(self, queries: SegmentArray, d: float, plan,
                *, pipeline: bool | None = None, on_group=None):
        """Engine-contract execution with routing accounting (the scheduler
        calls this once per batch group) — ``ShardedEngine.execute`` with a
        routed dispatcher substituted."""
        return self.engine.execute(
            queries, d, plan, pipeline=pipeline, on_group=on_group,
            dispatcher=self.dispatcher(queries.packed(), d))


class PodFallbackDispatcher:
    """Degraded route for a broken mesh (PR 10): execute a *shard plan*'s
    batches on the single device, off-mesh.

    When a pod drops out (:class:`~repro.core.errors.PodFailedError`),
    the broker's degradation ladder swaps a ticket's routed dispatcher
    for this one: each batch's whole candidate range — the dropped pod's
    ownership slice included — is evaluated by one ``ops.query_block``
    dispatch on the default device via the jnp oracle, sliced from the
    same (possibly permuted) packed layout the shard plan addresses, so
    the re-routed results stay byte-identical to the mesh's.  Slower —
    never wrong.
    """

    def __init__(self, engine: ShardedEngine, q_packed: np.ndarray,
                 d: float):
        self.engine = engine
        self.q_packed = q_packed
        self.d = float(d)

    def dispatch(self, batch, capacity: int) -> Dispatch:
        se = self.engine
        src = (se._packed_perm if se.plan_pruning == "hierarchical"
               else se._packed)
        e_slice = src[batch.cand_first:batch.cand_last + 1]
        q_slice = self.q_packed[batch.q_first:batch.q_last + 1]
        out = ops.query_block(
            e_slice, q_slice, np.float32(self.d), capacity=capacity,
            use_pallas=False, interpret=se.interpret,
            cand_blk=se.cand_blk, qry_blk=se.qry_blk,
            compaction="dense", pruning="none")
        return Dispatch(batch, capacity, out)

    def count(self, dp: Dispatch) -> int:
        return int(dp.out["count"])

    def retry_capacity(self, dp: Dispatch) -> int | None:
        # Shard-plan capacities are *per shard*; the single device holds
        # the whole batch, so the first dispatch may legitimately
        # overflow — one bucketed retry reaches the exact global count.
        count = self.count(dp)
        return bucket_capacity(count) if count > dp.capacity else None

    def marshal(self, dp: Dispatch, count: int) -> ResultSet | None:
        se = self.engine
        db = se.db
        ent = np.asarray(dp.out["entry_idx"])
        keep = ent >= 0
        if not keep.any():
            return None
        e_global = dp.batch.cand_first + ent[keep].astype(np.int64)
        if se.plan_pruning == "hierarchical" and se._perm is not None:
            e_global = se._perm[e_global]
        q_local = np.asarray(dp.out["query_idx"])[keep].astype(np.int64)
        return ResultSet(
            entry_idx=e_global,
            entry_traj=db.traj_id[e_global].astype(np.int64),
            entry_seg=db.seg_id[e_global].astype(np.int64),
            query_idx=dp.batch.q_first + q_local,
            t_enter=np.asarray(dp.out["t_enter"])[keep],
            t_exit=np.asarray(dp.out["t_exit"])[keep],
        )


class DistributedEngine:
    """Host-side driver for the sharded query step on a live mesh.

    Pads the candidate slice of each batch to a multiple of the candidate
    shard count, dispatches the sharded step, and assembles results.  Used
    for correctness tests on small CPU meshes and lowered (not run) on the
    production mesh in the dry-run.
    """

    def __init__(self, mesh: Mesh, db: SegmentArray,
                 cand_axes: Sequence[str] = ("data",), *,
                 num_bins: int = 1000, capacity_per_shard: int = 4096,
                 use_pallas: bool = False):
        from repro.core.index import TemporalBinIndex
        self.mesh = mesh
        self.db = db if db.is_sorted() else db.sort_by_tstart()
        self.index = TemporalBinIndex.build(self.db, num_bins)
        self._packed = self.db.packed()
        self.cand_axes = tuple(cand_axes)
        self.capacity = capacity_per_shard
        self._fn, self.ways = make_sharded_query_fn(
            mesh, self.cand_axes, capacity_per_shard, use_pallas=use_pallas)

    def query_batch(self, queries_packed: np.ndarray, qt0: float, qt1: float,
                    d: float) -> dict[str, np.ndarray]:
        first, last = self.index.candidate_range(qt0, qt1)
        c = last - first + 1
        if c <= 0:
            return {"entry_idx": np.zeros(0, np.int64),
                    "query_idx": np.zeros(0, np.int64),
                    "t_enter": np.zeros(0, np.float32),
                    "t_exit": np.zeros(0, np.float32)}
        pad = (-c) % self.ways
        e = self._packed[first:last + 1]
        if pad:
            t_pad = float(self.db.te.max()) + 1.0
            rows = np.zeros((pad, 8), np.float32)
            rows[:, 6] = rows[:, 7] = t_pad
            e = np.concatenate([e, rows], axis=0)
        out = self._fn(jnp.asarray(e), jnp.asarray(queries_packed),
                       np.float32(d))
        # One explicit sync for the whole shard-mapped batch; every host
        # read below is then a cheap copy of a ready buffer instead of a
        # hidden stall inside np.asarray (caught by SYNC001 otherwise).
        out = jax.block_until_ready(out)
        counts = np.asarray(out["count"])
        if np.any(counts > self.capacity):
            raise RuntimeError("per-shard result capacity overflow; retry "
                               "with larger capacity_per_shard")
        ent = np.asarray(out["entry_idx"])
        keep = ent >= 0
        return {"entry_idx": ent[keep].astype(np.int64) + first,
                "query_idx": np.asarray(out["query_idx"])[keep].astype(np.int64),
                "t_enter": np.asarray(out["t_enter"])[keep],
                "t_exit": np.asarray(out["t_exit"])[keep]}
