"""Pallas TPU kernels for the distance-threshold interaction tile.

TPU adaptation of the paper's ``GPUTRAJDISTSEARCH`` (Algorithm 1).  The GPU
version assigns one hardware thread per candidate entry segment, loops that
thread over the query batch, and short-circuits per-interaction branches.
On a TPU none of that maps: we instead tile the dense (C × Q) *interaction
matrix* over a 2-D grid and evaluate every interaction in a
(CAND_BLK × QRY_BLK) tile as fully branchless masked VPU arithmetic.

Layout choices (the important part):

* entries are blocked ``(CAND_BLK, 8)`` — candidate index is the sublane
  dimension, so an entry component column ``e[:, k:k+1]`` is a (C, 1) vector
  that broadcasts along lanes;
* queries are passed **transposed** ``(8, Q)`` and blocked ``(8, QRY_BLK)``
  — a query component row ``q[k:k+1, :]`` is a (1, Q) vector that broadcasts
  along sublanes.  Every per-pair quantity is then a rank-2 (C, Q) outer
  broadcast with **zero transposes inside the kernel**.
* grid is ``(C/CAND_BLK, Q/QRY_BLK)`` with the query axis innermost, so an
  entry block stays VMEM-resident while query blocks stream past it — the
  same reuse the GPU kernel gets from its thread-private candidate copy
  (paper §8.1.3's observation about Mixed-execution reuse).
* scalars (``d``, the inflated prune threshold, the running hit counter,
  the pruned-tile counter, the tile MBRs) live in SMEM; the flat result
  buffers are ``(rows, 128)`` VMEM planes, slot ``f`` at ``(f // 128,
  f % 128)``.

Two kernels share the interval math (:func:`_interval_math`):

* :func:`distthresh_pallas` — the dense kernel: materializes the full
  (C, Q) ``(t_enter, t_exit, hit)`` tile set in HBM; a host-side XLA pass
  compacts it (``ops.query_block(compaction="dense")``).
* :func:`distthresh_compact_pallas` — the **fused in-kernel compaction**
  kernel: the TPU grid runs its tiles *sequentially* on one core, so a
  running hit counter carried in SMEM across the grid is the deterministic
  analogue of the paper's §5 ``atomic_inc`` result append.  Each tile
  computes its hit mask and appends its hits, row-major over the tile, at
  the running counter's offset into capacity-bounded result buffers.
  Non-hits never touch HBM, and the exact total hit count comes back with
  the results, so overflow detection needs no dense pass.

The fused kernel has two append strategies (``append=``):

* ``"chunk"`` — compiles for the TPU.  Hits are located 128 result slots
  at a time with one-hot matrix products on the MXU (prefix sums against
  triangular ones matrices, row/column selection, and an exact three-way
  bf16 split for the f32 segment gathers — see :func:`_chunk_tile_body`),
  the hit pairs' intervals are recomputed on the gathered segments, and
  each 128-slot window is written with a lane rotation into two buffer
  rows (:func:`_append_window`).
* ``"rowloop"`` — a per-row ``fori_loop`` append over the dense tile
  intervals, kept as an interpret-mode cross-check of the chunk path's
  order.  It does not lower for the TPU (dynamic value slices and
  reductions to 1-D vectors) and raises when asked to compile.

Both emit the identical deterministic order.

Both fused kernels optionally take a **tile-level spatial early-out**
(PR 5, the device half of the two-level pruning subsystem — the host half
is ``repro.core.index.candidate_subranges``): per entry-tile and per
query-tile MBRs are precomputed upstream of the ``pallas_call``, and each
grid step first runs a ~10-scalar-op box-distance test against the
conservatively inflated threshold (``repro.core.index.prune_limit``) —
a tile whose boxes cannot come within ``d`` skips the full
(CAND_BLK × QRY_BLK) interval evaluation under ``@pl.when`` and bumps a
resident ``pruned`` tile counter instead (the unwritten result buffers
and running hit counter simply carry over to the next grid step).

:func:`distthresh_compact_live_pallas` (PR 7) goes one step further and
removes even that per-tile test from the device loop: the caller computes
the compacted **live-tile list** — the (entry-tile, query-tile) pairs
whose MBRs survive the same inflated-threshold test, in grid order — and
the kernel iterates a 1-D grid over *list slots*, with the tile
coordinates scalar-prefetched (``pltpu.PrefetchScalarGridSpec``) so the
BlockSpec index maps fetch exactly the live tiles' blocks.  Dead tiles
cost nothing; dead *slots* (list padding past ``n_live``) cost one scalar
compare.  Output order is identical to the full-grid kernels because the
list is sorted in grid order.

Every entry point takes ``interpret=None``, resolved by
:func:`resolve_interpret`: the Pallas interpreter on a CPU, the compiled
kernel everywhere else.

The interval math matches ``ref.interaction_tile`` bit-for-bit in float32
on the CPU; tests sweep shapes/dtypes and assert allclose against the
oracle, and the fused kernel's compacted rows are asserted equal to the
dense kernel's nonzero set (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default tile: 256×256 f32 tiles keep the ~14 live (C, Q) temporaries well
# under 16 MiB VMEM: 14 × 256 × 256 × 4 B ≈ 3.7 MiB.
DEFAULT_CAND_BLK = 256
DEFAULT_QRY_BLK = 256

#: Result slots per append window: one vreg row of lanes.  The chunk
#: append writes hits in windows of this many slots, so per-tile
#: compaction work scales with the hit count, not the tile size.
APPEND_BLK = 128

#: Largest tile side the chunk append supports: its in-tile prefix counts
#: (≤ the tile side) ride the MXU as bf16, exact only up to 256.
MAX_FUSED_BLK = 256

_A_EPS = 1e-12
_B_EPS = 1e-12


def resolve_interpret(interpret: bool | None = None, device=None) -> bool:
    """Whether Pallas kernels run in interpret mode.

    An explicit ``interpret`` wins.  Otherwise the platform of ``device``
    (default: ``jax.devices()[0]``, the device an engine places its work
    on) decides: the interpreter on ``cpu``, the compiled kernel on every
    other platform.
    """
    if interpret is not None:
        return bool(interpret)
    device = device if device is not None else jax.devices()[0]
    return device.platform == "cpu"


def _tile_intervals(e, q, d):
    """Interval math for one (C_BLK, Q_BLK) tile.

    Args:
      e: (C_BLK, 8) entry block.
      q: (8, Q_BLK) transposed query block.
      d: scalar threshold.

    Returns (t_enter, t_exit, hit) of shape (C_BLK, Q_BLK); hit is bool and
    the interval endpoints are zeroed where it is False.
    """
    # Entry components as (C, 1); query components as (1, Q) — every
    # per-pair quantity is a rank-2 outer broadcast.
    return _interval_math(tuple(e[:, k:k + 1] for k in range(8)),
                          tuple(q[k:k + 1, :] for k in range(8)),
                          d, e.dtype)


def _interval_math(e8, q8, d, dtype, *, zero_misses: bool = True):
    """Shared branchless interval solve over broadcastable components.

    ``e8`` / ``q8`` are the 8 packed-segment components (x0, y0, z0, x1,
    y1, z1, ts, te) of the entries and queries, in mutually broadcastable
    shapes; all outputs take the broadcast shape.  ``zero_misses=False``
    leaves the interval of a miss unzeroed.
    """
    ex0, ey0, ez0, ex1, ey1, ez1, ets, ete = e8
    qx0, qy0, qz0, qx1, qy1, qz1, qts, qte = q8

    # Velocities; zero-length temporal extents are static points.
    edt = ete - ets
    qdt = qte - qts
    e_safe = jnp.where(edt > 0, edt, 1.0)
    q_safe = jnp.where(qdt > 0, qdt, 1.0)
    e_live = (edt > 0).astype(dtype)
    q_live = (qdt > 0).astype(dtype)
    evx = (ex1 - ex0) / e_safe * e_live
    evy = (ey1 - ey0) / e_safe * e_live
    evz = (ez1 - ez0) / e_safe * e_live
    qvx = (qx1 - qx0) / q_safe * q_live
    qvy = (qy1 - qy0) / q_safe * q_live
    qvz = (qz1 - qz0) / q_safe * q_live

    # temporalIntersection: common interval [lo, hi], (C, Q).
    lo = jnp.maximum(ets, qts)
    hi = jnp.minimum(ete, qte)
    t_overlap = lo <= hi

    # Relative motion r(t) = dr0 + dv t with absolute-time anchors.
    dvx = evx - qvx
    dvy = evy - qvy
    dvz = evz - qvz
    drx = (ex0 - evx * ets) - (qx0 - qvx * qts)
    dry = (ey0 - evy * ets) - (qy0 - qvy * qts)
    drz = (ez0 - evz * ets) - (qz0 - qvz * qts)

    a = dvx * dvx + dvy * dvy + dvz * dvz
    b = 2.0 * (drx * dvx + dry * dvy + drz * dvz)
    c = drx * drx + dry * dry + drz * drz - d * d

    inf = jnp.asarray(jnp.inf, dtype)

    # calcTimeInterval: {t : a t^2 + b t + c <= 0} as [rlo, rhi].
    disc = b * b - 4.0 * a * c
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    safe_a = jnp.where(a > _A_EPS, a, 1.0)
    q_rlo = (-b - sq) / (2.0 * safe_a)
    q_rhi = (-b + sq) / (2.0 * safe_a)

    safe_b = jnp.where(jnp.abs(b) > _B_EPS, b, 1.0)
    root = -c / safe_b
    lin_rlo = jnp.where(b > 0, -inf, root)
    lin_rhi = jnp.where(b > 0, root, inf)

    is_quad = a > _A_EPS
    is_lin = (~is_quad) & (jnp.abs(b) > _B_EPS)

    rlo = jnp.where(is_quad, q_rlo, jnp.where(is_lin, lin_rlo, -inf))
    rhi = jnp.where(is_quad, q_rhi, jnp.where(is_lin, lin_rhi, inf))
    # Boolean algebra, not a select over booleans: Mosaic cannot lower an
    # i1 select (it round-trips through i8 and refuses the truncation).
    nonempty = ((is_quad & (disc >= 0.0))
                | (~is_quad & (is_lin | (c <= 0.0))))

    t_enter = jnp.maximum(rlo, lo)
    t_exit = jnp.minimum(rhi, hi)
    hit = t_overlap & nonempty & (t_enter <= t_exit)
    if not zero_misses:
        return t_enter, t_exit, hit

    zero = jnp.zeros((), dtype)
    return (jnp.where(hit, t_enter, zero), jnp.where(hit, t_exit, zero), hit)


def _smem():
    """Whole-array SMEM operand: scalars and the small MBR tables."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _distthresh_kernel(d_ref, entries_ref, queries_t_ref,
                       enter_ref, exit_ref, hit_ref):
    t_enter, t_exit, hit = _tile_intervals(entries_ref[...],
                                           queries_t_ref[...], d_ref[0, 0])
    enter_ref[...] = t_enter
    exit_ref[...] = t_exit
    hit_ref[...] = hit.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("cand_blk", "qry_blk", "interpret"))
def distthresh_pallas(entries: jnp.ndarray, queries_t: jnp.ndarray, d,
                      *, cand_blk: int = DEFAULT_CAND_BLK,
                      qry_blk: int = DEFAULT_QRY_BLK,
                      interpret: bool | None = None
                      ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Raw pallas_call over pre-padded inputs (dense outputs).

    Args:
      entries: (C, 8) with C a multiple of ``cand_blk``.
      queries_t: (8, Q) with Q a multiple of ``qry_blk`` (transposed packing).
      d: scalar threshold.

    Returns (t_enter, t_exit, hit) of shape (C, Q); hit is int8.
    """
    cc, eight = entries.shape
    assert eight == 8, entries.shape
    eight2, qq = queries_t.shape
    assert eight2 == 8, queries_t.shape
    assert cc % cand_blk == 0 and qq % qry_blk == 0, (cc, qq, cand_blk, qry_blk)
    grid = (cc // cand_blk, qq // qry_blk)
    dtype = entries.dtype
    d_arr = jnp.asarray(d, dtype).reshape(1, 1)

    out_shapes = (
        jax.ShapeDtypeStruct((cc, qq), dtype),
        jax.ShapeDtypeStruct((cc, qq), dtype),
        jax.ShapeDtypeStruct((cc, qq), jnp.int8),
    )
    out_spec = pl.BlockSpec((cand_blk, qry_blk), lambda i, j: (i, j))
    return pl.pallas_call(
        _distthresh_kernel,
        grid=grid,
        in_specs=[
            _smem(),                                            # d (scalar)
            pl.BlockSpec((cand_blk, 8), lambda i, j: (i, 0)),   # entries: stay on i
            pl.BlockSpec((8, qry_blk), lambda i, j: (0, j)),    # queries: stream on j
        ],
        out_specs=(out_spec, out_spec, out_spec),
        out_shape=out_shapes,
        interpret=resolve_interpret(interpret),
    )(d_arr, entries, queries_t)


# ----------------------------------------------------------------------
# Fused in-kernel compaction (the §5 atomic_inc analogue, sequential grid)
# ----------------------------------------------------------------------
def _window_width(append: str, qry_blk: int) -> int:
    """Slots one append writes from its start: one APPEND_BLK window for
    the chunk path, a whole tile row (in APPEND_BLK windows) for rowloop."""
    if append == "rowloop":
        return -(-qry_blk // APPEND_BLK) * APPEND_BLK
    return APPEND_BLK


def _buffer_rows(capacity: int, width: int) -> int:
    """Rows of a ``(rows, APPEND_BLK)`` result plane: an append may start
    at any slot ``<= capacity`` and writes ``width`` slots, its last
    APPEND_BLK window straddling two rows."""
    return (capacity + width) // APPEND_BLK + 1


def _init_buffers(bufs, count_ref, pruned_ref=None):
    """First grid step: index planes to -1, interval planes to 0, counters
    to 0."""
    e_idx_ref, q_idx_ref, enter_ref, exit_ref = bufs
    e_idx_ref[...] = jnp.full(e_idx_ref.shape, -1, jnp.int32)
    q_idx_ref[...] = jnp.full(q_idx_ref.shape, -1, jnp.int32)
    enter_ref[...] = jnp.zeros(enter_ref.shape, enter_ref.dtype)
    exit_ref[...] = jnp.zeros(exit_ref.shape, exit_ref.dtype)
    count_ref[0, 0] = 0
    if pruned_ref is not None:
        pruned_ref[0, 0] = 0


def _append_window(buf_ref, vec, dst):
    """Write the (1, APPEND_BLK) lane vector ``vec`` at flat slots ``[dst,
    dst + APPEND_BLK)`` of the ``(rows, APPEND_BLK)`` plane ``buf_ref``.

    The window straddles rows ``dst // APPEND_BLK`` and the one after: a
    lane rotation by ``dst % APPEND_BLK`` puts every slot on its lane, and
    one two-row read-modify-write keeps the slots before ``dst`` (earlier
    hits) and after the window as they were.
    """
    o = dst % APPEND_BLK
    row = dst // APPEND_BLK
    rolled = jnp.broadcast_to(pltpu.roll(vec, o, 1), (2, APPEND_BLK))
    sub = jax.lax.broadcasted_iota(jnp.int32, (2, APPEND_BLK), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (2, APPEND_BLK), 1)
    take = ((sub == 0) & (lane >= o)) | ((sub == 1) & (lane < o))
    window = buf_ref[pl.ds(row, 2), :]
    buf_ref[pl.ds(row, 2), :] = jnp.where(take, rolled, window)


def _exact_gather(x, onehot):
    """``x @ onehot`` for f32 ``x`` and a 0/1 ``onehot`` with at most one 1
    per column, exact on the MXU: ``x`` splits into three bf16 parts whose
    sum is ``x`` exactly, and each product selects a single value."""
    oh = onehot.astype(jnp.bfloat16)
    hi = x.astype(jnp.bfloat16)
    r1 = x - hi.astype(x.dtype)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(x.dtype)).astype(jnp.bfloat16)

    def dot(part):
        return jnp.dot(part, oh, preferred_element_type=jnp.float32)

    return (dot(hi) + dot(mid)) + dot(lo)


def _hit_mask(i, j, d, e_blk, q_blk, *, cand_blk, qry_blk, valid_c, valid_q):
    """Tile (i, j)'s hit mask with padding rows/cols masked out (broadcast
    vectors, no full index tiles), so pad×pad pairs never append."""
    _, _, hit = _tile_intervals(e_blk, q_blk, d)
    row_ok = (jax.lax.broadcasted_iota(jnp.int32, (cand_blk, 1), 0)
              + i * cand_blk) < valid_c
    col_ok = (jax.lax.broadcasted_iota(jnp.int32, (1, qry_blk), 1)
              + j * qry_blk) < valid_q
    return hit & row_ok & col_ok


def _chunk_tile_body(i, j, d_ref, entries_ref, queries_t_ref, bufs,
                     count_ref, *, cand_blk: int, qry_blk: int,
                     capacity: int, valid_c: int, valid_q: int):
    """Evaluate tile (i, j) and append its hits, row-major, in
    APPEND_BLK-slot windows (shared by the full-grid and live-tile kernels;
    ``i``/``j`` may be traced scalars read from a scalar-prefetch ref).

    Only the hit mask is computed over the tile; the intervals are
    recomputed, unmasked, for the ≤ APPEND_BLK hit pairs of each window
    (the tile's mask decided the hit; the recompute's own hit test must
    not zero it on a last-ulp difference).  The k-th hit of the tile
    (row-major) sits in row ``r_k`` = the number of rows
    whose inclusive hit prefix ends at or before ``k``, and in that row's
    column ``c_k`` = the number of columns whose in-row inclusive prefix is
    at most ``k``'s rank in the row.  Prefix counts are bf16 0/1 products
    against triangular ones matrices on the MXU (exact: every count is
    ≤ 256 per factor and < 2^24 in f32), and the per-slot row of prefixes
    and the slot's entry/query segments are one-hot products
    (:func:`_exact_gather`).  Zero-hit tiles skip all of it.
    """
    e_blk = entries_ref[...]                 # (cand_blk, 8), VMEM
    q_blk = queries_t_ref[...]               # (8, qry_blk), VMEM
    d = d_ref[0, 0]
    hit = _hit_mask(i, j, d, e_blk, q_blk, cand_blk=cand_blk,
                    qry_blk=qry_blk, valid_c=valid_c, valid_q=valid_q)
    hit_f = hit.astype(jnp.float32)
    tile_hits = jnp.sum(hit_f).astype(jnp.int32)
    offset = count_ref[0, 0]
    count_ref[0, 0] = offset + tile_hits

    @pl.when(tile_hits > 0)
    def _append():
        f32, bf16 = jnp.float32, jnp.bfloat16
        hit_b = hit_f.astype(bf16)

        def tri(n):                          # tri[a, b] = 1 iff b <= a
            return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
                    ).astype(bf16)

        # rowend[r]: hits in rows <= r, (cand_blk, 1); rowstart excludes r.
        rowend = jnp.sum(jnp.dot(tri(cand_blk), hit_b,
                                 preferred_element_type=f32),
                         axis=1, keepdims=True)
        rowstart = rowend - jnp.sum(hit_f, axis=1, keepdims=True)
        # rcum_t[c, r]: hits of row r in columns <= c, (qry_blk, cand_blk).
        rcum_t = jax.lax.dot_general(
            tri(qry_blk), hit_b, (((1,), (1,)), ((), ())),
            preferred_element_type=f32).astype(bf16)
        e_t = e_blk.T                        # (8, cand_blk)
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (cand_blk, APPEND_BLK),
                                           0).astype(f32)
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (qry_blk, APPEND_BLK),
                                           0).astype(f32)
        zero = jnp.zeros((), bufs[2].dtype)

        def _window(k, carry):
            base = k * APPEND_BLK
            slot = base + jax.lax.broadcasted_iota(jnp.int32,
                                                   (1, APPEND_BLK), 1)
            slot_f = slot.astype(f32)
            r_k = jnp.sum((rowend <= slot_f).astype(f32), axis=0,
                          keepdims=True)                 # (1, APPEND_BLK)
            row_oh = row_ids == r_k                      # (cand_blk, APPEND_BLK)
            rank = slot_f - jnp.sum(jnp.where(row_oh, rowstart, 0.0),
                                    axis=0, keepdims=True)
            prefix = jnp.dot(rcum_t, row_oh.astype(bf16),
                             preferred_element_type=f32)  # (qry_blk, APPEND_BLK)
            c_k = jnp.sum((prefix <= rank).astype(f32), axis=0,
                          keepdims=True)
            col_oh = col_ids == c_k                      # (qry_blk, APPEND_BLK)
            e_g = _exact_gather(e_t, row_oh)             # (8, APPEND_BLK)
            q_g = _exact_gather(q_blk, col_oh)
            t_enter, t_exit, _ = _interval_math(
                tuple(e_g[m:m + 1, :] for m in range(8)),
                tuple(q_g[m:m + 1, :] for m in range(8)), d, e_blk.dtype,
                zero_misses=False)
            valid = slot < tile_hits             # slots past the hit count
            dst = offset + base

            @pl.when(dst <= capacity)            # overflow: drop, keep count
            def _():
                e_idx_ref, q_idx_ref, enter_ref, exit_ref = bufs
                _append_window(e_idx_ref, jnp.where(
                    valid, i * cand_blk + r_k.astype(jnp.int32), -1), dst)
                _append_window(q_idx_ref, jnp.where(
                    valid, j * qry_blk + c_k.astype(jnp.int32), -1), dst)
                _append_window(enter_ref, jnp.where(valid, t_enter, zero),
                               dst)
                _append_window(exit_ref, jnp.where(valid, t_exit, zero), dst)

            return carry

        jax.lax.fori_loop(0, (tile_hits + APPEND_BLK - 1) // APPEND_BLK,
                          _window, 0)


def _rowloop_tile_body(i, j, d_ref, entries_ref, queries_t_ref, bufs,
                       count_ref, *, cand_blk: int, qry_blk: int,
                       capacity: int, valid_c: int, valid_q: int):
    """Evaluate tile (i, j) and row-append its hits (shared by the
    full-grid and live-tile kernels; interpret mode only)."""
    e_blk = entries_ref[...]
    q_blk = queries_t_ref[...]
    d = d_ref[0, 0]
    t_enter, t_exit, _ = _tile_intervals(e_blk, q_blk, d)
    hit2 = _hit_mask(i, j, d, e_blk, q_blk, cand_blk=cand_blk,
                     qry_blk=qry_blk, valid_c=valid_c, valid_q=valid_q)

    hit_i = hit2.astype(jnp.int32)
    row_cum = jnp.cumsum(hit_i, axis=1)      # (cand_blk, qry_blk)
    offset = count_ref[0, 0]

    # Per-slot and per-column index planes shared by every row
    # iteration.
    slot_plane = jax.lax.broadcasted_iota(jnp.int32,
                                          (qry_blk, qry_blk), 0)
    col_plane = jax.lax.broadcasted_iota(jnp.int32,
                                         (qry_blk, qry_blk), 1)
    slot_vec = jax.lax.broadcasted_iota(jnp.int32, (qry_blk, 1), 0)[:, 0]
    zero = jnp.zeros((), bufs[2].dtype)
    width = _window_width("rowloop", qry_blk)
    n_win = width // APPEND_BLK

    def _windows(v, pad):
        v = jnp.pad(v, (0, width - qry_blk), constant_values=pad)
        return [v[w * APPEND_BLK:(w + 1) * APPEND_BLK][None, :]
                for w in range(n_win)]

    def _row_body(r, dst):
        rh = jax.lax.dynamic_slice(hit_i, (r, 0), (1, qry_blk))
        rcum = jax.lax.dynamic_slice(row_cum, (r, 0), (1, qry_blk))
        rent = jax.lax.dynamic_slice(t_enter, (r, 0), (1, qry_blk))
        rext = jax.lax.dynamic_slice(t_exit, (r, 0), (1, qry_blk))
        n_r = rcum[0, qry_blk - 1]
        # sel[s, c] = 1 iff column c is the row's (s+1)-th hit:
        # compaction becomes a masked reduction over columns — no
        # gathers anywhere.
        sel = (rcum == slot_plane + 1) & (rh > 0)
        sel_f = sel.astype(rent.dtype)
        comp_col = jnp.sum(jnp.where(sel, col_plane, 0), axis=1)
        comp_ent = jnp.sum(sel_f * rent, axis=1)
        comp_ext = jnp.sum(sel_f * rext, axis=1)
        valid = slot_vec < n_r
        planes = (
            (jnp.where(valid, i * cand_blk + r, -1).astype(jnp.int32), -1),
            (jnp.where(valid, j * qry_blk + comp_col,
                       -1).astype(jnp.int32), -1),
            (jnp.where(valid, comp_ent, zero), zero),
            (jnp.where(valid, comp_ext, zero), zero))

        @pl.when((n_r > 0) & (dst <= capacity))  # overflow: drop,
        def _():                                  # keep count
            for ref, (vals, pad) in zip(bufs, planes):
                for w, win in enumerate(_windows(vals, pad)):
                    _append_window(ref, win, dst + w * APPEND_BLK)

        return dst + n_r

    end = jax.lax.fori_loop(0, cand_blk, _row_body, offset)
    count_ref[0, 0] = end


def _distthresh_compact_kernel(d_ref, entries_ref, queries_t_ref, *refs,
                               body, cand_blk: int, qry_blk: int,
                               capacity: int, valid_c: int, valid_q: int,
                               prune: bool):
    """One grid step: evaluate a tile, append its hits at the running offset.

    The four ``(rows, APPEND_BLK)`` result planes use constant index maps,
    so they stay resident across the sequential grid; the SMEM ``count``
    output is the running hit counter.  Appends use the *overwritten-tail*
    scheme: a tile writes ``ceil(tile_hits / window)`` fixed-width windows
    whose slots are the compacted hits, the last window's tail being pad
    slots; the next tile's first window starts at ``offset + tile_hits``,
    overwriting the tail.  The planes carry one window of slack beyond
    ``capacity`` so a window starting at any offset ``<= capacity`` fits;
    once the counter passes ``capacity`` appends are skipped (the caller
    sees ``count > capacity`` and retries larger — the counter itself keeps
    accumulating, so ``count`` is always exact).

    With ``prune`` the refs after the three inputs start with the SMEM
    entry-tile / query-tile MBR tables (flattened ``(tiles * 8,)``) and the
    inflated threshold: a tile whose boxes are farther apart than the
    threshold skips the interval math entirely and bumps the ``pruned``
    counter instead — the unwritten result buffers and ``count`` simply
    carry over to the next grid step.
    """
    if prune:
        embr_ref, qmbr_ref, dprune_ref, *refs = refs
    *bufs, count_ref, pruned_ref = refs
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        _init_buffers(bufs, count_ref, pruned_ref)

    def _body():
        body(i, j, d_ref, entries_ref, queries_t_ref, bufs, count_ref,
             cand_blk=cand_blk, qry_blk=qry_blk, capacity=capacity,
             valid_c=valid_c, valid_q=valid_q)

    if not prune:
        _body()
        return
    live = _tile_mbr_live(embr_ref, qmbr_ref, dprune_ref, i, j)

    @pl.when(jnp.logical_not(live))
    def _skip():
        pruned_ref[0, 0] = pruned_ref[0, 0] + 1

    pl.when(live)(_body)


def _tile_mbr_live(embr_ref, qmbr_ref, dprune_ref, i, j):
    """The tile-level early-out test: squared box distance between entry
    tile ``i``'s and query tile ``j``'s MBRs vs the (conservatively
    inflated) threshold.

    The MBR rows are laid out ``(lo_x, lo_y, lo_z, hi_x, hi_y, hi_z, _, _)``
    and flattened, 8 scalars per tile; all-padding tiles carry the empty
    box (``lo=+inf, hi=-inf``) whose gap is ``inf`` — always pruned.  A
    handful of scalar ops per tile, against a full (CAND_BLK × QRY_BLK)
    interval evaluation saved.
    """
    gap2 = jnp.zeros((), jnp.float32)
    for ax in range(3):
        elo, ehi = embr_ref[i * 8 + ax], embr_ref[i * 8 + 3 + ax]
        qlo, qhi = qmbr_ref[j * 8 + ax], qmbr_ref[j * 8 + 3 + ax]
        g = jnp.maximum(jnp.maximum(qlo - ehi, elo - qhi), 0.0)
        gap2 = gap2 + g * g
    dp = dprune_ref[0, 0]
    return gap2 <= dp * dp


#: append strategies accepted by :func:`distthresh_compact_pallas`.
APPEND_MODES = ("chunk", "rowloop")


def _append_body(append: str, interpret: bool, cand_blk: int, qry_blk: int):
    """The tile body for ``append`` — validated, and refused where it
    cannot run (an explicit error, never a silent switch of strategy)."""
    if append not in APPEND_MODES:
        raise ValueError(f"unknown append mode {append!r}; "
                         f"choose from {APPEND_MODES}")
    if append == "rowloop":
        if not interpret:
            raise NotImplementedError(
                "append='rowloop' (compaction='fused_rowloop') does not "
                "lower for the TPU; it runs only in interpret mode. Use "
                "compaction='fused' (append='chunk') on the chip.")
        return _rowloop_tile_body
    if max(cand_blk, qry_blk) > MAX_FUSED_BLK:
        raise ValueError(f"append='chunk' supports tiles up to "
                         f"{MAX_FUSED_BLK}×{MAX_FUSED_BLK}; got "
                         f"{cand_blk}×{qry_blk}")
    return _chunk_tile_body


def _result_planes(rows: int, dtype, index_map):
    """Specs and shapes of the four resident ``(rows, APPEND_BLK)`` result
    planes (entry index, query index, t_enter, t_exit) and the SMEM hit
    counter."""
    spec = pl.BlockSpec((rows, APPEND_BLK), index_map)
    shapes = tuple(jax.ShapeDtypeStruct((rows, APPEND_BLK), dt)
                   for dt in (jnp.int32, jnp.int32, dtype, dtype))
    return ((spec,) * 4 + (_smem(),),
            shapes + (jax.ShapeDtypeStruct((1, 1), jnp.int32),))


def _compiler_params(rows: int, dims: tuple[str, ...]):
    """Sequential grid (the running counter needs it) and enough scoped
    VMEM for the double-buffered resident result planes on top of the
    tile temporaries."""
    planes = 2 * 4 * rows * APPEND_BLK * 4
    return pltpu.CompilerParams(
        dimension_semantics=dims,
        vmem_limit_bytes=min(32 * 2**20 + planes, 120 * 2**20))


def _flat(plane, capacity: int):
    return plane.reshape(-1)[:capacity]


@functools.partial(jax.jit, static_argnames=(
    "capacity", "cand_blk", "qry_blk", "valid_c", "valid_q", "interpret",
    "append"))
def distthresh_compact_pallas(entries: jnp.ndarray, queries_t: jnp.ndarray, d,
                              *, capacity: int,
                              cand_blk: int = DEFAULT_CAND_BLK,
                              qry_blk: int = DEFAULT_QRY_BLK,
                              valid_c: int | None = None,
                              valid_q: int | None = None,
                              interpret: bool | None = None,
                              append: str = "chunk",
                              e_mbr: jnp.ndarray | None = None,
                              q_mbr: jnp.ndarray | None = None,
                              d_prune=None):
    """Fused distance-threshold kernel with in-kernel result compaction.

    Args:
      entries: (C, 8) with C a multiple of ``cand_blk``.
      queries_t: (8, Q) with Q a multiple of ``qry_blk`` (transposed packing).
      d: scalar threshold.
      capacity: result-buffer slots; hits beyond it are dropped (``count``
        still reports the exact total, so callers detect overflow exactly).
      valid_c / valid_q: number of *real* (non-padding) rows/cols; pairs at
        or beyond them are masked out of the result.  Default: all.
      interpret: Pallas interpret mode; ``None`` resolves from the default
        device (:func:`resolve_interpret`).
      append: ``"chunk"`` — one-hot MXU rank selection in APPEND_BLK-slot
        windows (the path that compiles for the TPU).  ``"rowloop"`` — the
        per-row append loop, interpret mode only (same results, same
        determinism).
      e_mbr / q_mbr / d_prune: the tile-level spatial early-out (PR 5).
        ``e_mbr`` is (C/cand_blk, 8) — per entry tile ``(lo_xyz, hi_xyz,
        0, 0)`` — and ``q_mbr`` (Q/qry_blk, 8) the same per query tile,
        precomputed upstream of the ``pallas_call`` (``ops._tile_mbrs``)
        and read from SMEM as scalars.  A grid tile whose boxes are farther
        apart than ``d_prune`` (the conservatively inflated threshold, see
        ``repro.core.index.prune_limit``) skips all interval math and
        increments the ``pruned`` counter.  All three must be given
        together, or all omitted (no early-out).

    Returns ``(entry_idx, query_idx, t_enter, t_exit, count, pruned)``:
    four (capacity,) buffers — int32 indices (-1 pad) and interval
    endpoints (0 pad) — plus the exact scalar int32 hit count and the
    number of grid tiles the MBR early-out skipped (0 without pruning
    inputs).  Output order is deterministic (and identical across append
    modes *and* pruning on/off — pruned tiles contribute no rows): tiles
    in grid order (query tiles innermost), row-major within each tile.
    """
    interpret = resolve_interpret(interpret)
    body = _append_body(append, interpret, cand_blk, qry_blk)
    prune = e_mbr is not None
    if (q_mbr is None) == prune or (d_prune is None) == prune:
        raise ValueError("e_mbr, q_mbr and d_prune must be given together "
                         "(tile early-out armed) or all omitted")
    cc, eight = entries.shape
    assert eight == 8, entries.shape
    eight2, qq = queries_t.shape
    assert eight2 == 8, queries_t.shape
    assert cc % cand_blk == 0 and qq % qry_blk == 0, (cc, qq, cand_blk, qry_blk)
    valid_c = cc if valid_c is None else valid_c
    valid_q = qq if valid_q is None else valid_q
    grid = (cc // cand_blk, qq // qry_blk)
    dtype = entries.dtype
    d_arr = jnp.asarray(d, dtype).reshape(1, 1)

    rows = _buffer_rows(capacity, _window_width(append, qry_blk))
    out_specs, out_shapes = _result_planes(rows, dtype,
                                           lambda i, j: (0, 0))
    in_specs = [
        _smem(),                                            # d (scalar)
        pl.BlockSpec((cand_blk, 8), lambda i, j: (i, 0)),   # entries
        pl.BlockSpec((8, qry_blk), lambda i, j: (0, j)),    # queries
    ]
    args = [d_arr, entries, queries_t]
    if prune:
        in_specs += [_smem(), _smem(), _smem()]   # tile MBRs, inflated d
        args += [jnp.asarray(e_mbr, jnp.float32).reshape(-1),
                 jnp.asarray(q_mbr, jnp.float32).reshape(-1),
                 jnp.asarray(d_prune, jnp.float32).reshape(1, 1)]
    kernel = functools.partial(
        _distthresh_compact_kernel, body=body, cand_blk=cand_blk,
        qry_blk=qry_blk, capacity=capacity, valid_c=valid_c,
        valid_q=valid_q, prune=prune)
    e_idx, q_idx, t_enter, t_exit, count, pruned = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs + (_smem(),),
        out_shape=out_shapes + (jax.ShapeDtypeStruct((1, 1), jnp.int32),),
        compiler_params=_compiler_params(rows, ("arbitrary",) * 2),
        interpret=interpret,
    )(*args)
    return (_flat(e_idx, capacity), _flat(q_idx, capacity),
            _flat(t_enter, capacity), _flat(t_exit, capacity), count[0, 0],
            pruned[0, 0])


# ----------------------------------------------------------------------
# Live-tile dispatch (PR 7): ragged grid over a precomputed tile list
# ----------------------------------------------------------------------
def _distthresh_compact_live_kernel(ti_ref, tj_ref, nlive_ref, d_ref,
                                    entries_ref, queries_t_ref, *refs,
                                    body, cand_blk: int, qry_blk: int,
                                    capacity: int, valid_c: int,
                                    valid_q: int):
    """One live-list slot: evaluate tile ``(ti[s], tj[s])`` if the slot is
    live, else fall through (one scalar compare).

    The first three refs are the scalar-prefetched live-tile list: the
    entry-tile ids, query-tile ids, and the live count (list entries past
    it are padding that points at tile (0, 0) so the prefetch stays in
    bounds).  The same scalar refs drive the entry/query BlockSpec index
    maps, so the pipeline fetches exactly the live tiles' blocks — a dead
    tile never leaves HBM.  Because the list is sorted in grid order
    (query tiles innermost) and the append bodies are shared with the
    full-grid kernels, the output rows are byte-identical to theirs.
    """
    *bufs, count_ref = refs
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        _init_buffers(bufs, count_ref)

    @pl.when(s < nlive_ref[0])
    def _run():
        body(ti_ref[s], tj_ref[s], d_ref, entries_ref, queries_t_ref, bufs,
             count_ref, cand_blk=cand_blk, qry_blk=qry_blk,
             capacity=capacity, valid_c=valid_c, valid_q=valid_q)


@functools.partial(jax.jit, static_argnames=(
    "capacity", "cand_blk", "qry_blk", "valid_c", "valid_q", "interpret",
    "append"))
def distthresh_compact_live_pallas(entries: jnp.ndarray,
                                   queries_t: jnp.ndarray, d,
                                   tile_i: jnp.ndarray, tile_j: jnp.ndarray,
                                   n_live: jnp.ndarray, *, capacity: int,
                                   cand_blk: int = DEFAULT_CAND_BLK,
                                   qry_blk: int = DEFAULT_QRY_BLK,
                                   valid_c: int | None = None,
                                   valid_q: int | None = None,
                                   interpret: bool | None = None,
                                   append: str = "chunk"):
    """Fused compaction kernel driven by a precomputed live-tile list.

    Where :func:`distthresh_compact_pallas` walks the full
    ``(C/cand_blk, Q/qry_blk)`` grid and pays a per-tile box test, this
    variant iterates a **1-D grid over list slots**: the caller has
    already run the inflated-threshold box test (host-side via
    ``ops._host_live_tiles``, or in-graph via ``ops._jit_live_tiles``
    when tracing forbids host work) and hands over the surviving
    (entry-tile, query-tile) pairs in grid order.  The tile ids are
    scalar-prefetched (``pltpu.PrefetchScalarGridSpec``) so the entry and
    query BlockSpec index maps read them directly — the pipeline fetches
    exactly the live tiles' blocks and a dead tile costs *nothing*; a dead
    *slot* (padding past ``n_live``) costs one scalar compare.

    Args:
      entries / queries_t / d: as in :func:`distthresh_compact_pallas`.
      tile_i / tile_j: (S,) int32 entry-/query-tile ids of the live tiles,
        sorted in full-grid order (query tiles innermost); slots past
        ``n_live`` must point at a valid tile (0 is fine) — they are
        skipped but still prefetched.
      n_live: (1,) int32 count of live slots (``<= S``).  Traced, so one
        compiled kernel serves every list that fits the same padded ``S``.
      capacity / valid_c / valid_q / interpret / append: as in
        :func:`distthresh_compact_pallas`.

    Returns ``(entry_idx, query_idx, t_enter, t_exit, count)``; no
    ``pruned`` counter — the caller already knows ``num_tiles - n_live``.
    Output order is byte-identical to the full-grid kernels' (the live
    list is in grid order and pruned tiles contribute no rows).
    """
    interpret = resolve_interpret(interpret)
    body = _append_body(append, interpret, cand_blk, qry_blk)
    cc, eight = entries.shape
    assert eight == 8, entries.shape
    eight2, qq = queries_t.shape
    assert eight2 == 8, queries_t.shape
    assert cc % cand_blk == 0 and qq % qry_blk == 0, (cc, qq, cand_blk, qry_blk)
    (n_slots,) = tile_i.shape
    assert tile_j.shape == (n_slots,) and n_slots >= 1, (tile_i.shape,
                                                        tile_j.shape)
    valid_c = cc if valid_c is None else valid_c
    valid_q = qq if valid_q is None else valid_q
    dtype = entries.dtype
    d_arr = jnp.asarray(d, dtype).reshape(1, 1)

    rows = _buffer_rows(capacity, _window_width(append, qry_blk))
    out_specs, out_shapes = _result_planes(
        rows, dtype, lambda s, ti, tj, nl: (0, 0))
    kernel = functools.partial(
        _distthresh_compact_live_kernel, body=body, cand_blk=cand_blk,
        qry_blk=qry_blk, capacity=capacity, valid_c=valid_c,
        valid_q=valid_q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # tile_i, tile_j, n_live
        grid=(n_slots,),
        in_specs=[
            _smem(),                                            # d
            # The scalar-prefetched list drives the block fetches: slot s
            # pulls entry block ti[s] and query block tj[s].
            pl.BlockSpec((cand_blk, 8), lambda s, ti, tj, nl: (ti[s], 0)),
            pl.BlockSpec((8, qry_blk), lambda s, ti, tj, nl: (0, tj[s])),
        ],
        out_specs=out_specs,
    )
    e_idx, q_idx, t_enter, t_exit, count = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=_compiler_params(rows, ("arbitrary",)),
        interpret=interpret,
    )(tile_i.astype(jnp.int32), tile_j.astype(jnp.int32),
      n_live.astype(jnp.int32), d_arr, entries, queries_t)
    return (_flat(e_idx, capacity), _flat(q_idx, capacity),
            _flat(t_enter, capacity), _flat(t_exit, capacity), count[0, 0])
