"""The distance-threshold kernel's work, and the chip's published peaks.

A roofline share must read the same work whatever implements the kernel,
so the work is counted from the data, never from the program's counters:

* **Pairs** — the (entry, query) segment pairs whose closed time extents
  overlap (``reference.overlap_count``: two ``searchsorted`` over the
  entries' start and end times).  The paper's kernel evaluates exactly
  these.  This is the kernel's work only where spatial pruning removes
  nothing, which holds for both configurations here (GALAXY and
  RANDWALK-EXP are active over the whole space at every instant); a
  prunable deployment needs its own definition.
* **Operations** — pairs times :data:`OPS_PER_PAIR`.
* **Bytes** — every segment read once (the entries whose extents overlap
  any query segment of the set, and the query segments), 32 bytes each,
  plus every hit row written once, 16 bytes.
"""
from __future__ import annotations

import numpy as np

#: Published peaks per chip, keyed by JAX's ``device_kind``.  The interval
#: math is float32 on the vector unit, whose peak is not published; the
#: matrix unit's bf16 peak stands in, so the compute side of a share read
#: against it understates.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                   "bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip"),
    },
}

#: Bytes of one segment as the kernel reads it: 8 float32 components.
SEGMENT_BYTES = 32
#: Bytes of one hit row as the kernel writes it: entry index and query
#: index (int32), t_enter and t_exit (float32).
ROW_BYTES = 16


def ops_per_pair() -> int:
    """Arithmetic operations per temporally overlapping pair.

    The paper's interval equations (§5, ``calcTimeInterval``), counting
    each add, multiply, compare, min/max, division and square root as one,
    with each segment's velocity and time-anchored origin computed once
    per segment (not per pair):

    ====================================================  ===
    overlap ``lo = max(ts)``, ``hi = min(te)``, ``lo <= hi``    3
    relative velocity ``dv = v_e - v_q`` (3 components)       3
    relative origin ``dr = r_e - r_q`` (3 components)         3
    ``a = dv . dv``  (3 multiplies, 2 adds)                   5
    ``b = 2 dr . dv`` (3 multiplies, 2 adds, 1 multiply)      6
    ``c = dr . dr - d^2`` (3 multiplies, 2 adds, 1 subtract)  6
    discriminant ``b^2 - 4ac`` and its sign                   5
    square root                                               1
    roots ``(-b -+ sqrt) / 2a``: 2a, two adds, two divides    5
    clamps ``max(rlo, lo)``, ``min(rhi, hi)``                 2
    hit test ``t_enter <= t_exit``                            1
    ====================================================  ===

    Total 40.
    """
    return 3 + 3 + 3 + 5 + 6 + 6 + 5 + 1 + 5 + 2 + 1


OPS_PER_PAIR = ops_per_pair()


def entries_touched(e_ts: np.ndarray, e_te: np.ndarray, q_ts: np.ndarray,
                    q_te: np.ndarray) -> int:
    """Entries whose closed extent overlaps at least one query segment's:
    entry ``e`` overlaps some query iff, among the queries starting no
    later than ``e`` ends, the latest end is no earlier than ``e``'s
    start."""
    if len(q_ts) == 0 or len(e_ts) == 0:
        return 0
    order = np.argsort(q_ts, kind="stable")
    qs = np.asarray(q_ts, np.float64)[order]
    reach = np.maximum.accumulate(np.asarray(q_te, np.float64)[order])
    k = np.searchsorted(qs, np.asarray(e_te, np.float64), side="right")
    ok = k > 0
    return int(np.sum(reach[np.maximum(k - 1, 0)][ok]
                      >= np.asarray(e_ts, np.float64)[ok]))


def work(pairs: int, entries: int, queries: int, hits: int
         ) -> tuple[float, float]:
    """(operations, bytes) of one evaluation of a query set."""
    ops = float(pairs) * OPS_PER_PAIR
    nbytes = float(entries + queries) * SEGMENT_BYTES + float(hits) * ROW_BYTES
    return ops, nbytes


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown chip is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/roofline.py "
                       f"with their source") from None


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> float | None:
    """Percent of the roofline: the least time the chip could take (the
    larger of ops over peak FLOP/s and bytes over peak bandwidth) over the
    measured kernel time.  ``None`` when no kernel time was measured."""
    if seconds <= 0 or (ops <= 0 and nbytes <= 0):
        return None
    p = peaks(device_kind)
    bound = max(ops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * bound / seconds
