"""Jit'd public wrappers around the distance-threshold interaction kernel.

Two layers:

* :func:`interaction_tiles` — pad → ``pallas_call`` (or the jnp oracle) →
  crop.  Dense (C, Q) outputs.
* :func:`query_block` — the full per-batch device computation: interaction
  evaluation + deterministic result compaction (the TPU replacement for the
  paper's ``atomic_inc`` append, §5).  Returns fixed-capacity result
  buffers plus the true hit count, so the caller can detect overflow and
  retry with a larger capacity (mirroring the paper's §5 re-attempt note).

``query_block`` has two compaction strategies (``compaction=``):

* ``"fused"`` (default on the Pallas path) — the hits are compacted *inside*
  the kernel (``distthresh_compact_pallas``): a running counter carried
  across the sequential TPU grid plays the role of the paper's atomic
  counter, and each tile appends its masked-prefix-sum-compacted hits
  directly into the flat result buffers.  Per-interaction HBM traffic is
  zero for non-hits, and the exact count comes back with the results.
  A kernel that fails to lower raises to the caller; the other strategy
  never stands in for it.
* ``"dense"`` — the two-pass fallback (and the only strategy for the jnp
  oracle path): the dense tile's hit mask and intervals are materialized,
  then compacted with an XLA cumsum + scatter.  Kept as the validation
  baseline: tests assert the strategies produce identical hit sets.

The two strategies emit different (both deterministic) row orders —
``"dense"`` is row-major over the full (C, Q) block, ``"fused"`` is
row-major within each kernel tile, tiles in grid order — so consumers that
need a canonical order sort downstream (``ResultSet.sorted_canonical``,
``QueryResult.from_result_set``).

Shape discipline: ``query_block``'s jit is keyed on the row counts it is
handed, so a dispatcher that meets many batch shapes pads its blocks to a
ladder of lengths first (the shard dispatcher always,
``repro.core.distributed``; the single-device one under
``ExecutionPolicy.dispatch_ladder``, ``repro.core.engine``), with pad rows
built by :func:`pad_block` at the instants of :func:`pad_instants`:
temporal extents outside the data range, so they can never hit, and
correctness does not depend on cropping.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults
from repro.core import spans
from repro.kernels import distthresh as _dt
from repro.kernels import ref
from repro.kernels.distthresh import (DEFAULT_CAND_BLK, DEFAULT_QRY_BLK,
                                      distthresh_pallas)

#: compaction strategies accepted by :func:`query_block`.
COMPACTIONS = ("fused", "dense")

#: pruning strategies accepted by :func:`query_block`: ``"spatial"``
#: (PR 5) arms the fused kernels' tile-level MBR early-out — a box test
#: per grid tile inside the kernel; ``"hierarchical"`` (PR 7) moves that
#: test *out* of the device loop entirely: the box test runs once per
#: dispatch (host-side numpy, or in-graph under an outer trace) and the
#: fused kernel iterates only the compacted **live-tile list** via a
#: ragged scalar-prefetched grid (``distthresh_compact_live_pallas``) —
#: dead tiles cost nothing instead of a per-tile predicate.  ``"none"``
#: disables tile-level pruning.  The dense two-phase path (and the jnp
#: oracle) has no tile loop to skip, so pruning is a documented no-op
#: there — it stays the validated unpruned baseline.  None of the modes
#: ever changes the result set (the box test uses the conservatively
#: inflated ``prune_limit`` threshold), only the work.
PRUNINGS = ("spatial", "hierarchical", "none")


def pad_instants(t_end: float, queries: np.ndarray) -> tuple[float, float]:
    """The instants of a dispatcher's host pad rows: entry pads at the
    first, query pads at the second.  Both lie beyond ``t_end`` (the
    database's last instant) and every query's, and one apart, so no pad
    row overlaps a real row or the other side's pads in time."""
    t = max(float(t_end), float(queries[:, 7].max(initial=t_end))) + 1.0
    return t, t + 1.0


def pad_block(x: np.ndarray, rows: int, t: float) -> np.ndarray:
    """Packed rows ``x`` followed by pad rows up to ``rows``: each a
    zero-length extent at instant ``t`` (:func:`pad_instants`), at the
    origin."""
    n = x.shape[0]
    if rows == n:
        return x
    out = np.zeros((rows, 8), x.dtype)
    out[:, 6:8] = t
    out[:n] = x
    return out


def _pad_rows(x: jnp.ndarray, multiple: int, pad_t: jnp.ndarray) -> jnp.ndarray:
    """Pad (N, 8) packed segments to a row multiple with non-hitting rows."""
    n = x.shape[0]
    target = ((max(n, 1) + multiple - 1) // multiple) * multiple
    if target == n:
        return x
    pad = jnp.zeros((target - n, 8), x.dtype)
    pad = pad.at[:, 6].set(pad_t).at[:, 7].set(pad_t)
    return jnp.concatenate([x, pad], axis=0)


def _pad_time(entries: jnp.ndarray, queries: jnp.ndarray) -> jnp.ndarray:
    """A time strictly greater than every real t — padding rows never hit.

    Callers must guard against zero-row inputs (``jnp.max`` of an empty
    array is an error); see the empty-input short-circuits below.
    """
    return jnp.maximum(jnp.max(entries[:, 7]), jnp.max(queries[:, 7])) + 1.0


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "cand_blk", "qry_blk"))
def interaction_tiles(entries: jnp.ndarray, queries: jnp.ndarray, d,
                      *, use_pallas: bool = True,
                      interpret: bool | None = None,
                      cand_blk: int = DEFAULT_CAND_BLK,
                      qry_blk: int = DEFAULT_QRY_BLK):
    """Dense all-pairs distance-threshold intervals.

    Args:
      entries: (C, 8) packed entry segments (no padding required).
      queries: (Q, 8) packed query segments.
      d: scalar threshold.
      use_pallas: route through the Pallas kernel or the pure-jnp oracle
        (faster on CPU; identical semantics).
      interpret: Pallas interpret mode; ``None`` resolves from the device
        (``distthresh.resolve_interpret``: interpreted only on a CPU).

    Returns (t_enter, t_exit, hit) of shape (C, Q), hit bool.
    """
    c, q = entries.shape[0], queries.shape[0]
    if c == 0 or q == 0:
        # Zero-row guard: the pad-time computation below takes jnp.max over
        # the temporal extents, which errors on empty inputs (reachable by
        # direct kernel users; the engine never dispatches empty batches).
        dtype = jnp.promote_types(entries.dtype, jnp.float32)
        empty = jnp.zeros((c, q), dtype)
        return empty, empty, jnp.zeros((c, q), bool)
    if not use_pallas:
        return ref.interaction_tile(entries, queries, d)
    pad_t = _pad_time(entries, queries)
    ep = _pad_rows(entries, cand_blk, pad_t)
    qp = _pad_rows(queries, qry_blk, pad_t)
    t_enter, t_exit, hit = distthresh_pallas(
        ep, qp.T, d, cand_blk=cand_blk, qry_blk=qry_blk, interpret=interpret)
    return (t_enter[:c, :q], t_exit[:c, :q], hit[:c, :q].astype(bool))


def _empty_block(capacity: int, dtype) -> dict:
    return {"entry_idx": jnp.full((capacity,), -1, jnp.int32),
            "query_idx": jnp.full((capacity,), -1, jnp.int32),
            "t_enter": jnp.zeros((capacity,), dtype),
            "t_exit": jnp.zeros((capacity,), dtype),
            "count": jnp.zeros((), jnp.int32),
            "pruned_tiles": jnp.zeros((), jnp.int32),
            "num_tiles": jnp.zeros((), jnp.int32)}


def _pad_tiles(mbr: np.ndarray, rows: int, blk: int) -> np.ndarray:
    """A tile-MBR table over real rows, extended to the tiles of ``rows``
    with the empty box (±inf), whose gap is ``inf``: a tile of host pad
    rows, which can never hit, is always skipped."""
    nt = -(-rows // blk)
    if nt == mbr.shape[0]:
        return mbr
    out = np.zeros((nt, 8), np.float32)
    out[:, 0:3] = np.inf
    out[:, 3:6] = -np.inf
    out[:mbr.shape[0]] = mbr
    return out


def _host_tile_mbrs(packed: np.ndarray, blk: int) -> np.ndarray:
    """Per-tile spatial MBRs of packed segments, host-side (numpy).

    Returns ``(ceil(n/blk), 8)`` float32 rows ``(lo_xyz, hi_xyz, 0, 0)``
    over each run of ``blk`` rows of the *to-be-padded* layout (padding
    rows excluded; an all-padding tail tile would not exist since tiles
    beyond ``ceil(n/blk)`` are never emitted — pad rows merely shorten the
    last tile's membership).  A linearly moving segment never leaves the
    box spanned by its endpoints, so the tile box bounds every member's
    position over its whole temporal extent.
    """
    n = packed.shape[0]
    nt = (max(n, 1) + blk - 1) // blk
    lo = np.minimum(packed[:, 0:3], packed[:, 3:6]).astype(np.float64)
    hi = np.maximum(packed[:, 0:3], packed[:, 3:6]).astype(np.float64)
    starts = np.arange(0, nt * blk, blk)
    starts = np.minimum(starts, max(n - 1, 0))
    tlo = np.minimum.reduceat(lo, starts, axis=0)
    thi = np.maximum.reduceat(hi, starts, axis=0)
    out = np.zeros((nt, 8), np.float32)
    out[:, 0:3] = tlo
    out[:, 3:6] = thi
    return out


def _host_prune_threshold(d, entries: np.ndarray,
                          queries: np.ndarray) -> float:
    """The conservatively inflated tile-prune threshold at dispatch time:
    ``repro.core.index.prune_limit`` (the one exactness-critical slack
    formula) evaluated at this dispatch's largest coordinate magnitude."""
    from repro.core.index import prune_limit
    scale = max(float(np.abs(entries[:, 0:6]).max(initial=0.0)),
                float(np.abs(queries[:, 0:6]).max(initial=0.0)), 1.0)
    return prune_limit(float(d), scale)


def _jit_tile_mbrs(packed: jnp.ndarray, blk: int, n_valid: int) -> jnp.ndarray:
    """In-graph twin of :func:`_host_tile_mbrs` over the *padded* packed
    array (used when ``query_block`` runs under an outer trace, e.g. the
    ``shard_map`` pod step, where host gating is impossible).  Padding rows
    are masked out; an all-padding tile gets the empty box (±inf) whose
    gap is ``inf`` — always skipped."""
    nt = packed.shape[0] // blk
    r = packed.reshape(nt, blk, 8)
    lo = jnp.minimum(r[..., 0:3], r[..., 3:6])
    hi = jnp.maximum(r[..., 0:3], r[..., 3:6])
    valid = (jnp.arange(nt * blk).reshape(nt, blk, 1)) < n_valid
    lo = jnp.where(valid, lo, jnp.inf).min(axis=1)
    hi = jnp.where(valid, hi, -jnp.inf).max(axis=1)
    out = jnp.zeros((nt, 8), packed.dtype)
    return out.at[:, 0:3].set(lo).at[:, 3:6].set(hi)


def _jit_prune_threshold(d, entries: jnp.ndarray, queries: jnp.ndarray):
    """In-graph twin of :func:`_host_prune_threshold` — must mirror
    ``repro.core.index.prune_limit`` (traced values, so it cannot
    delegate); tests pin the three-way agreement via the byte-identical
    pruning-on/off acceptance suite."""
    d = jnp.asarray(d, jnp.float32)
    scale = jnp.maximum(jnp.maximum(jnp.max(jnp.abs(entries[:, 0:6])),
                                    jnp.max(jnp.abs(queries[:, 0:6]))), 1.0)
    err = 4e-6 * scale * scale
    slack = jnp.minimum(err / jnp.maximum(2.0 * d, 1e-12), jnp.sqrt(err))
    return d + 1e-5 * d + slack + 1e-9


def _slot_bucket(n: int, minimum: int = 64) -> int:
    """Bucketed live-tile-list length: next power of two ≥ ``max(n,
    minimum)``, so the jit cache sees O(log) distinct slot counts rather
    than one compiled kernel per dispatch-specific list length."""
    return 1 << (max(n, minimum) - 1).bit_length()


def _host_live_tiles(entries: np.ndarray, queries: np.ndarray, d,
                     cand_blk: int, qry_blk: int):
    """Host-side live-tile-list preparation for one dispatch (PR 7).

    Runs the same inflated-threshold box test as :func:`_host_tile_prune`
    over every (entry-tile, query-tile) pair, but instead of shipping the
    per-tile MBRs into the kernel it compacts the *surviving* pairs into
    a flat list in grid order (``np.nonzero`` over the row-major live
    matrix — query tiles innermost, exactly the full-grid iteration
    order, which is what keeps the live kernel's output byte-identical).

    Returns ``None`` when **no** tile pair would be skipped — the caller
    then dispatches the classic unarmed kernel, which beats even the live
    kernel's one-compare-per-slot on unprunable workloads — otherwise
    ``(tile_i, tile_j, n_live, num_tiles)`` with the slot arrays padded
    to a :func:`_slot_bucket` length (padding points at tile 0; the
    kernel skips slots past ``n_live``).

    Sync audit: numpy on the planner's pre-upload packed slices, same as
    ``_host_tile_prune`` — no device work, the dispatch stays async.
    """
    from repro.core.index import mbr_gap2
    e_mbr = _host_tile_mbrs(entries, cand_blk)
    q_mbr = _host_tile_mbrs(queries, qry_blk)
    d_prune = _host_prune_threshold(d, entries, queries)
    gap2 = mbr_gap2(e_mbr[:, None, 0:3], e_mbr[:, None, 3:6],
                    q_mbr[None, :, 0:3], q_mbr[None, :, 3:6])
    live = gap2 <= d_prune * d_prune
    if live.all():
        return None
    ti, tj = np.nonzero(live)
    n_live = int(ti.size)
    n_slots = _slot_bucket(n_live)
    tile_i = np.zeros((n_slots,), np.int32)
    tile_j = np.zeros((n_slots,), np.int32)
    tile_i[:n_live] = ti
    tile_j[:n_live] = tj
    return tile_i, tile_j, np.array([n_live], np.int32), int(live.size)


def _jit_live_tiles(e_mbr: jnp.ndarray, q_mbr: jnp.ndarray, d_prune):
    """In-graph twin of :func:`_host_live_tiles` (outer-trace callers,
    e.g. the ``shard_map`` pod step — each pod builds its own list from
    its resident shard).  Traced shapes are static, so the "list" is the
    full grid with live slots stably sorted to the front (grid order
    preserved) and ``n_live`` traced — dead slots cost the live kernel
    one scalar compare each, still far cheaper than a box test plus
    predicated tile body.  Empty (±inf) boxes from all-padding tiles get
    an infinite gap and sort dead."""
    g = jnp.maximum(jnp.maximum(q_mbr[None, :, 0:3] - e_mbr[:, None, 3:6],
                                e_mbr[:, None, 0:3] - q_mbr[None, :, 3:6]),
                    0.0)
    gap2 = jnp.sum(g * g, axis=-1)
    live = (gap2 <= d_prune * d_prune).reshape(-1)
    nt_q = q_mbr.shape[0]
    order = jnp.argsort(jnp.logical_not(live))   # jnp argsort is stable
    tile_i = (order // nt_q).astype(jnp.int32)
    tile_j = (order % nt_q).astype(jnp.int32)
    n_live = jnp.sum(live.astype(jnp.int32)).reshape(1)
    return tile_i, tile_j, n_live


def _host_tile_prune(entries: np.ndarray, queries: np.ndarray, d,
                     cand_blk: int, qry_blk: int):
    """Host-side tile-prune preparation for one dispatch.

    Computes the per-tile entry/query MBRs and the inflated threshold with
    numpy (microseconds on dispatch-sized slices — the dispatch stays
    async: no device work, no sync), evaluates the box test over every
    tile pair, and returns ``(e_mbr, q_mbr, d_prune)`` **only when at
    least one tile pair would actually be skipped** — otherwise ``None``,
    and the caller dispatches the classic unarmed kernel.  This gating is
    what keeps the early-out strictly profitable: on workloads with no
    exploitable space/time structure (GALAXY/RANDWALK) the armed kernel's
    per-tile predicate and extra operands are pure overhead (measurably so
    in interpret mode), so they are only paid when tiles will be pruned.

    Sync audit: ``entries``/``queries`` here are the planner's packed
    *numpy* slices (pre-upload), never device arrays — ``query_block``
    gates on that, so nothing in this helper can block on the device and
    SYNC001 has no purchase on it.
    """
    from repro.core.index import mbr_gap2
    e_mbr = _host_tile_mbrs(entries, cand_blk)
    q_mbr = _host_tile_mbrs(queries, qry_blk)
    d_prune = _host_prune_threshold(d, entries, queries)
    gap2 = mbr_gap2(e_mbr[:, None, 0:3], e_mbr[:, None, 3:6],
                    q_mbr[None, :, 0:3], q_mbr[None, :, 3:6])
    if not np.any(gap2 > d_prune * d_prune):
        return None
    return e_mbr, q_mbr, np.float32(d_prune)


def query_block(entries: jnp.ndarray, queries: jnp.ndarray, d, *,
                capacity: int, use_pallas: bool = True,
                interpret: bool | None = None,
                cand_blk: int = DEFAULT_CAND_BLK, qry_blk: int = DEFAULT_QRY_BLK,
                compaction: str = "fused", pruning: str = "none",
                valid_rows: tuple[int, int] | None = None):
    """Interaction evaluation + deterministic compaction into flat buffers.

    Returns a dict with:
      ``entry_idx``  (capacity,) int32 — row index into ``entries`` (-1 pad)
      ``query_idx``  (capacity,) int32 — row index into ``queries`` (-1 pad)
      ``t_enter``    (capacity,) f32
      ``t_exit``     (capacity,) f32
      ``count``      () int32 — true number of hits (may exceed capacity ⇒
                     caller retries with larger capacity)
      ``pruned_tiles`` () int32 — grid tiles the spatial early-out skipped
      ``num_tiles``  () int32 — grid tiles the dispatch comprised (both 0
                     on paths without a tile loop — dense / jnp oracle)

    ``compaction="fused"`` routes through the in-kernel compaction kernel
    when ``use_pallas`` is set (the jnp oracle has no kernel to fuse into,
    so it always uses the dense two-phase pass); ``"dense"`` forces the
    two-phase pass.  A kernel that fails to lower raises; no other
    strategy is tried.  All orders are deterministic; see the module
    docstring for how they differ.  ``interpret=None`` resolves from the
    device (``distthresh.resolve_interpret``).

    ``pruning="spatial"`` arms the fused kernels' tile-level MBR early-out:
    per-tile entry/query bounding boxes and the (inflated — see
    ``_host_prune_threshold``) threshold are precomputed host-side at
    dispatch (numpy, no device work, dispatch stays async) and the armed
    kernel is only used when the box test finds at least one skippable
    tile pair — otherwise the classic kernel runs with zero overhead
    (``_host_tile_prune``).  Inside an outer trace (``shard_map``) the
    boxes are computed in-graph instead.

    ``pruning="hierarchical"`` runs the *same* box test but outside the
    device loop: the surviving tile pairs are compacted into a live-tile
    list (``_host_live_tiles``; in-graph ``_jit_live_tiles`` under an
    outer trace) and the ragged scalar-prefetched kernel
    (``distthresh_compact_live_pallas``) iterates only that list — dead
    tiles are never fetched and cost nothing, and a fully-dead dispatch
    short-circuits without touching the device.  The same
    nothing-skippable gate routes to the classic unarmed kernel, so on
    unprunable workloads this mode pays zero per-tile overhead (vs the
    armed spatial kernel's per-tile predicate).

    No pruning mode ever changes the result set, only the work; the dense
    path ignores both.

    ``valid_rows=(c, q)`` says that only the first ``c`` entries and ``q``
    queries are real and the rest are a dispatcher's host pad rows
    (:func:`pad_block`).  The host box tests then see the real rows alone,
    as they would unpadded, and every tile of pad rows is skipped.

    The bytes of the host arrays handed to the jit call (the upload) are
    added to the ``h2d_bytes`` counter of the caller's recorder
    (``repro.core.spans``).
    """
    if compaction not in COMPACTIONS:
        raise ValueError(f"unknown compaction {compaction!r}; "
                         f"choose from {COMPACTIONS}")
    if pruning not in PRUNINGS:
        raise ValueError(f"unknown pruning {pruning!r}; "
                         f"choose from {PRUNINGS}")
    real_e, real_q = entries, queries
    if valid_rows is not None:
        real_e, real_q = entries[:valid_rows[0]], queries[:valid_rows[1]]
    # Chaos hook (PR 10), gated to host-side dispatch so it can never fire
    # inside an outer trace (shard_map passes tracers for entries/queries).
    if faults.armed() and isinstance(entries, np.ndarray):
        faults.inject("ops.query_block", compaction=compaction,
                      pruning=pruning, use_pallas=use_pallas,
                      rows=int(real_e.shape[0]))
    prune_arrays = {}
    host_prunable = (use_pallas and compaction == "fused"
                     and isinstance(entries, np.ndarray)
                     and isinstance(queries, np.ndarray)
                     and real_e.shape[0] and real_q.shape[0])
    if pruning == "spatial" and host_prunable:
        prep = _host_tile_prune(real_e, real_q, d, cand_blk, qry_blk)
        if prep is None:
            pruning = "none"           # nothing skippable: unarmed kernel
        else:
            e_mbr, q_mbr, d_prune = prep
            prune_arrays = dict(
                e_mbr=_pad_tiles(e_mbr, entries.shape[0], cand_blk),
                q_mbr=_pad_tiles(q_mbr, queries.shape[0], qry_blk),
                d_prune=d_prune)
    elif pruning == "hierarchical" and host_prunable:
        prep = _host_live_tiles(real_e, real_q, d, cand_blk, qry_blk)
        if prep is None:
            pruning = "none"           # nothing skippable: unarmed kernel
        else:
            tile_i, tile_j, n_live, num_tiles = prep
            if int(n_live[0]) == 0:
                # Every tile pruned: no device work at all.
                dtype = jnp.promote_types(entries.dtype, jnp.float32)
                out = _empty_block(capacity, dtype)
                out["pruned_tiles"] = jnp.asarray(num_tiles, jnp.int32)
                out["num_tiles"] = jnp.asarray(num_tiles, jnp.int32)
                return out
            prune_arrays = dict(tile_i=tile_i, tile_j=tile_j,
                                n_live=n_live)
    spans.count("h2d_bytes", sum(
        a.nbytes for a in (entries, queries, d, *prune_arrays.values())
        if isinstance(a, (np.ndarray, np.generic))))
    return _query_block_jit(entries, queries, d, capacity=capacity,
                            use_pallas=use_pallas, interpret=interpret,
                            cand_blk=cand_blk, qry_blk=qry_blk,
                            compaction=compaction, pruning=pruning,
                            **prune_arrays)


@functools.partial(jax.jit, static_argnames=("capacity", "use_pallas",
                                             "interpret", "cand_blk",
                                             "qry_blk", "compaction",
                                             "pruning"))
def _query_block_jit(entries: jnp.ndarray, queries: jnp.ndarray, d, *,
                     capacity: int, use_pallas: bool,
                     interpret: bool | None,
                     cand_blk: int, qry_blk: int, compaction: str,
                     pruning: str = "none", e_mbr=None, q_mbr=None,
                     d_prune=None, tile_i=None, tile_j=None, n_live=None):
    """Jitted :func:`query_block` body for one *resolved* compaction.
    ``e_mbr``/``q_mbr``/``d_prune`` carry host-precomputed tile-prune
    operands (see ``_host_tile_prune``) and ``tile_i``/``tile_j``/
    ``n_live`` a host-precomputed live-tile list (``_host_live_tiles``);
    with ``pruning="spatial"``/``"hierarchical"`` and no precomputed
    operands they are derived in-graph (outer-trace callers).
    """
    c, q = entries.shape[0], queries.shape[0]
    compute_dtype = jnp.promote_types(entries.dtype, jnp.float32)
    if c == 0 or q == 0:
        return _empty_block(capacity, compute_dtype)

    if compaction == "fused" and use_pallas:
        pad_t = _pad_time(entries, queries)
        ep = _pad_rows(entries, cand_blk, pad_t)
        qp = _pad_rows(queries, qry_blk, pad_t)
        num_tiles = (ep.shape[0] // cand_blk) * (qp.shape[0] // qry_blk)
        if pruning == "hierarchical":
            if tile_i is None:
                tile_i, tile_j, n_live = _jit_live_tiles(
                    _jit_tile_mbrs(ep, cand_blk, c),
                    _jit_tile_mbrs(qp, qry_blk, q),
                    _jit_prune_threshold(d, entries, queries))
            (e_idx, q_idx, t_enter, t_exit,
             count) = _dt.distthresh_compact_live_pallas(
                ep, qp.T, d, tile_i, tile_j, n_live, capacity=capacity,
                cand_blk=cand_blk, qry_blk=qry_blk, valid_c=c, valid_q=q,
                interpret=interpret)
            pruned = jnp.asarray(num_tiles, jnp.int32) - n_live[0]
            return {"entry_idx": e_idx, "query_idx": q_idx,
                    "t_enter": t_enter, "t_exit": t_exit, "count": count,
                    "pruned_tiles": pruned,
                    "num_tiles": jnp.asarray(num_tiles, jnp.int32)}
        prune_kw = {}
        if e_mbr is not None:
            prune_kw = dict(e_mbr=e_mbr, q_mbr=q_mbr, d_prune=d_prune)
        elif pruning == "spatial":
            prune_kw = dict(e_mbr=_jit_tile_mbrs(ep, cand_blk, c),
                            q_mbr=_jit_tile_mbrs(qp, qry_blk, q),
                            d_prune=_jit_prune_threshold(d, entries,
                                                         queries))
        (e_idx, q_idx, t_enter, t_exit, count,
         pruned) = _dt.distthresh_compact_pallas(
            ep, qp.T, d, capacity=capacity, cand_blk=cand_blk,
            qry_blk=qry_blk, valid_c=c, valid_q=q, interpret=interpret,
            **prune_kw)
        return {"entry_idx": e_idx, "query_idx": q_idx,
                "t_enter": t_enter, "t_exit": t_exit, "count": count,
                "pruned_tiles": pruned,
                "num_tiles": jnp.asarray(num_tiles, jnp.int32)}

    # Dense compaction (the pre-fusion path): evaluate the dense tile, then
    # compact hits and their intervals with an XLA prefix sum + scatter.
    # The intervals are the tile's own, never recomputed: in float32 the
    # interval of a near-tangent pair is ill-conditioned, and a second
    # evaluation that XLA rounds differently can move it by a tenth of a
    # time unit (seen on a TPU v5e at S2's size).
    t_enter, t_exit, hit = interaction_tiles(
        entries, queries, d, use_pallas=use_pallas, interpret=interpret,
        cand_blk=cand_blk, qry_blk=qry_blk)
    flat_hit = hit.reshape(-1)
    # Prefix-sum compaction (the atomic_inc replacement).
    pos = jnp.cumsum(flat_hit.astype(jnp.int32)) - 1
    count = jnp.sum(flat_hit.astype(jnp.int32))
    # Scatter destinations: hits beyond capacity (overflow) and non-hits are
    # routed out of bounds and dropped.
    dest = jnp.where(flat_hit, pos, capacity)
    dest = jnp.where(dest < capacity, dest, capacity)
    lin = jnp.arange(c * q, dtype=jnp.int32)

    def compact(values, fill):
        return jnp.full((capacity,), fill, values.dtype).at[dest].set(
            values, mode="drop")

    out_e = compact(lin // q, -1)
    out_q = compact(lin % q, -1)
    out_ent = compact(t_enter.reshape(-1), 0)
    out_ext = compact(t_exit.reshape(-1), 0)
    return {"entry_idx": out_e, "query_idx": out_q,
            "t_enter": out_ent, "t_exit": out_ext, "count": count,
            "pruned_tiles": jnp.zeros((), jnp.int32),
            "num_tiles": jnp.zeros((), jnp.int32)}


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "cand_blk", "qry_blk"))
def count_hits(entries: jnp.ndarray, queries: jnp.ndarray, d, *,
               use_pallas: bool = True, interpret: bool | None = None,
               cand_blk: int = DEFAULT_CAND_BLK,
               qry_blk: int = DEFAULT_QRY_BLK) -> jnp.ndarray:
    """Number of result-set items without materializing them (for sizing)."""
    _, _, hit = interaction_tiles(entries, queries, d, use_pallas=use_pallas,
                                  interpret=interpret, cand_blk=cand_blk,
                                  qry_blk=qry_blk)
    return jnp.sum(hit.astype(jnp.int32))
