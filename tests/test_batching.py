"""Batch-generation algorithms: Fig. 2 arithmetic + partition invariants."""
import hashlib
import time

import numpy as np
import pytest
from _hypothesis_compat import given, settings
from _hypothesis_compat import strategies as st

import repro
from conftest import random_segments
from repro.core import batching, spans
from repro.core.index import TemporalBinIndex
from repro.core.segments import SegmentArray
from repro.data import trajgen


def _fig2_world():
    """Reconstruction of the paper's Fig. 2: 4 entry bins with 6/3/3/2
    segments and temporal extents chosen so that 10-query batches overlap
    bins exactly as in the figure (120/60 interactions for batches 2/3,
    300 when merged — the §4 worked arithmetic)."""
    # bins over [0, 12): width 3. Entry segments per bin, extents inside bin.
    counts = [6, 3, 3, 2]
    ts, te = [], []
    for b, c in enumerate(counts):
        for i in range(c):
            t0 = 3.0 * b + 0.1 + 0.2 * i
            ts.append(t0)
            te.append(3.0 * b + 2.9)         # stays within its bin
    ts, te = np.array(ts, np.float32), np.array(te, np.float32)
    n = len(ts)
    z = np.zeros(n, np.float32)
    db = SegmentArray(z, z.copy(), z.copy(), z.copy(), z.copy(), z.copy(),
                      ts, te, np.arange(n, dtype=np.int32),
                      np.zeros(n, np.int32))
    idx = TemporalBinIndex.build(db, num_bins=4)
    return db, idx


def _queries(ts_list):
    ts = np.asarray(ts_list, np.float32)
    n = len(ts)
    z = np.zeros(n, np.float32)
    return SegmentArray(z, z.copy(), z.copy(), z.copy(), z.copy(), z.copy(),
                        ts, ts + 0.05, np.arange(n, dtype=np.int32),
                        np.zeros(n, np.int32))


class TestFig2Arithmetic:
    def test_batch_interaction_counts(self):
        """Batch 2 spans bins B0–B2 ⇒ 10·(6+3+3) = 120; batch 3 spans
        B1–B2 ⇒ 10·(3+3) = 60; merged 20-batch ⇒ 20·(6+3+3) = 240… the
        paper's text example merges batches overlapping B0..B2 for 300 with
        an extra bin; we verify the structural rule numInts = |Q|·|E|."""
        db, idx = _fig2_world()
        q2 = _queries(np.linspace(2.0, 8.0, 10))       # overlaps B0,B1,B2
        plan = batching.periodic(idx, q2, 10)
        assert plan.num_batches == 1
        assert plan.batches[0].num_ints == 10 * (6 + 3 + 3)

        q3 = _queries(np.linspace(4.0, 8.0, 10))       # overlaps B1,B2
        plan3 = batching.periodic(idx, q3, 10)
        assert plan3.batches[0].num_ints == 10 * (3 + 3)

        merged = SegmentArray.concatenate([q2, q3]).sort_by_tstart()
        planm = batching.periodic(idx, merged, 20)
        assert planm.batches[0].num_ints == 20 * (6 + 3 + 3)
        # merging created 300−120−60 = 60·? extra wasteful interactions
        extra = planm.total_interactions - (plan.total_interactions
                                            + plan3.total_interactions)
        assert extra == 20 * 12 - 120 - 60

    def test_free_merge_detected_by_greedy(self):
        """Two batches overlapping the same bins merge for free (paper §6:
        'no extra wasteful interactions will be generated')."""
        db, idx = _fig2_world()
        q = _queries(np.linspace(0.2, 2.0, 20))        # all within B0
        plan = batching.greedysetsplit_min(idx, q, bound=1)
        assert plan.num_batches == 1                   # all free merges
        assert plan.total_interactions == 20 * 6


ALGO_CASES = [
    ("periodic", {"s": 16}),
    ("setsplit-fixed", {"num_batches": 8}),
    ("setsplit-max", {"max_size": 32}),
    ("setsplit-minmax", {"min_size": 4, "max_size": 32}),
    ("greedysetsplit-min", {"bound": 8}),
    ("greedysetsplit-max", {"bound": 32}),
]


class TestPartitionInvariants:
    @pytest.mark.parametrize("name,kw", ALGO_CASES)
    def test_contiguous_exhaustive_partition(self, name, kw):
        rng = np.random.default_rng(1)
        db = random_segments(rng, 400)
        queries = random_segments(rng, 97)
        idx = TemporalBinIndex.build(db, num_bins=64)
        plan = batching.ALGORITHMS[name](idx, queries, **kw)
        # batches tile [0, len) contiguously in order
        expect = 0
        for b in plan.batches:
            assert b.q_first == expect
            assert b.q_last >= b.q_first
            expect = b.q_last + 1
        assert expect == len(queries)

    def test_setsplit_fixed_reaches_target(self):
        rng = np.random.default_rng(2)
        db = random_segments(rng, 300)
        queries = random_segments(rng, 60)
        idx = TemporalBinIndex.build(db, num_bins=32)
        plan = batching.setsplit_fixed(idx, queries, 7)
        assert plan.num_batches == 7

    def test_setsplit_minmax_respects_min(self):
        rng = np.random.default_rng(3)
        db = random_segments(rng, 300)
        queries = random_segments(rng, 80)
        idx = TemporalBinIndex.build(db, num_bins=32)
        plan = batching.setsplit_minmax(idx, queries, 5, 40)
        if plan.num_batches > 1:
            assert plan.sizes().min() >= 5

    def test_greedy_min_respects_bound(self):
        rng = np.random.default_rng(4)
        db = random_segments(rng, 300)
        queries = random_segments(rng, 80)
        idx = TemporalBinIndex.build(db, num_bins=32)
        plan = batching.greedysetsplit_min(idx, queries, 6)
        # every batch except possibly the last reaches the bound
        assert all(s >= 6 for s in plan.sizes()[:-1])

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 1000), s=st.integers(1, 50))
    def test_periodic_num_ints_consistent(self, seed, s):
        """num_ints recorded per batch equals size × index candidates."""
        rng = np.random.default_rng(seed)
        db = random_segments(rng, 200)
        queries = random_segments(rng, 41)
        idx = TemporalBinIndex.build(db, num_bins=16)
        plan = batching.periodic(idx, queries, s)
        for b in plan.batches:
            qt1 = float(queries.te[b.q_first:b.q_last + 1].max())
            assert b.qt1 == pytest.approx(qt1)
            assert b.num_ints == b.size * idx.num_candidates(b.qt0, b.qt1)

    def test_merging_never_decreases_interactions(self):
        """Fig. 3's monotonicity: larger periodic batches ⇒ ≥ interactions."""
        rng = np.random.default_rng(5)
        db = random_segments(rng, 500)
        queries = random_segments(rng, 96)
        idx = TemporalBinIndex.build(db, num_bins=64)
        totals = [batching.periodic(idx, queries, s).total_interactions
                  for s in (1, 4, 16, 48, 96)]
        assert all(a <= b for a, b in zip(totals, totals[1:]))


# ----------------------------------------------------------------------
# GREEDYSETSPLIT's windowed scan against the merge-by-merge loop
# ----------------------------------------------------------------------
def _greedy_reference(index, queries, bound, variant, counter=None):
    """GREEDYSETSPLIT as the merge-by-merge loop over ``_BatchState``
    (one priced merge and one array copy per merge): the plan
    ``batching._greedy`` must reproduce batch for batch."""
    t_begin = time.perf_counter()
    st = batching._BatchState(index, queries, counter)
    # Phase 1 (lines 4–11): single pass of free merges.  A merge is free iff
    # numInts(merge) == numInts(B[i]) + numInts(B[i+1]).
    i = 0
    while i < len(st) - 1:
        if st.merged_ints(i) == st.num_ints[i] + st.num_ints[i + 1]:
            st.merge_at(i)
        else:
            i += 1
    # Phase 2 (lines 13–20): constraint pass.
    i = 0
    while i < len(st) - 1:
        if variant == "min":
            if st.sizes[i] < bound:
                st.merge_at(i)
            else:
                i += 1
        else:  # "max": paper swaps the test and the clauses — merge while the
            # current batch has not yet exceeded the bound.  The bound is soft
            # (the merge that crosses it is still performed), exactly as the
            # literal transformation of line 14 dictates.
            if st.sizes[i] > bound:
                i += 1
            else:
                st.merge_at(i)
    return batching._finish(f"greedysetsplit-{variant}", {"bound": bound},
                            st, t_begin)


def _galaxy_world():
    """GALAXY stars on one shared time grid, d = 1: the segments of one
    time step share their extent, so free merges run 6 queries long."""
    segs = trajgen.galaxy(num_traj=40, num_segments=60, seed=3).segments
    db = segs.sort_by_tstart()
    rows = np.nonzero(np.isin(db.traj_id, [1, 7, 12, 20, 28, 33]))[0]
    return db, db.take(rows), 1.0


def _randwalk_world():
    """Random segments at random times, d = 2: few merges are free."""
    rng = np.random.default_rng(11)
    return random_segments(rng, 600), random_segments(rng, 150), 2.0


def _wide_world():
    """Queries around and beyond the database's box, every ninth one
    60 times farther out, d = 0.5: a prefix union's coordinate scale, and
    so its prune threshold, jumps above the database's when it takes in a
    far query, and differs from the whole set's before it does."""
    rng = np.random.default_rng(12)
    db = random_segments(rng, 600)
    q = random_segments(rng, 150, box=45.0)
    far = np.arange(4, len(q), 9)
    for c in ("xs", "ys", "zs", "xe", "ye", "ze"):
        getattr(q, c)[far] *= 60.0
    return db, q, 0.5


def _single_world():
    rng = np.random.default_rng(13)
    return random_segments(rng, 200), random_segments(rng, 1), 2.0


def _skewed_world():
    """RANDWALK-EXP in small (``bench/datasets/randwalk_exp.py``'s
    distributions): 150 walks of exponential length starting over [0, 20],
    the queries ten of them, d = 50.  Activity decays with time, so the
    singleton price changes every few queries: the scan runs many lanes."""
    db = trajgen.randwalk_exp(num_traj=150, seed=7).segments.sort_by_tstart()
    rows = np.nonzero(np.isin(db.traj_id, np.unique(db.traj_id)[::15]))[0]
    return db, db.take(rows), 50.0


WORLDS = {"galaxy": _galaxy_world, "randwalk": _randwalk_world,
          "wide": _wide_world, "single": _single_world,
          "skewed": _skewed_world}
#: (level, max_subranges); level None prices the temporal-only count.
PRICINGS = [(None, None), ("bin", None), ("bin", 2), ("box", None),
            ("box", 2)]


def _plan_inputs(world, level, cap):
    db, queries, d = WORLDS[world]()
    index = TemporalBinIndex.build(db, num_bins=48, kboxes=4)
    counter = (None if level is None else batching.SpatialInteractionCounter(
        index, queries, d, level=level, max_subranges=cap))
    return index, queries, counter


def _batches(plan):
    return (plan.algorithm, plan.params, plan.batches)


@pytest.mark.parametrize("world", sorted(WORLDS))
@pytest.mark.parametrize("level,cap", PRICINGS)
@pytest.mark.parametrize("bound", [1, 7, 100_000])
@pytest.mark.parametrize("variant", ["min", "max"])
def test_greedy_scan_matches_merge_loop(world, level, cap, bound, variant):
    index, queries, counter = _plan_inputs(world, level, cap)
    want = _greedy_reference(index, queries, bound, variant, counter)
    got = batching.ALGORITHMS[f"greedysetsplit-{variant}"](
        index, queries, bound, counter=counter)
    assert _batches(got) == _batches(want)


class _MidrangeCounter:
    """Prices a batch at the sum of its MBR's lowest and highest x, each
    row on its own: not monotone in the union, so queries at x = 5 and
    x = 15 (singletons 10 and 30) merge free at 20 each, across a change
    of the singleton price."""

    def __init__(self, queries):
        self.qlo, self.qhi = queries.mbrs()

    def row_counts(self, qt0, qt1, lo, hi):
        return (np.asarray(lo)[:, 0] + np.asarray(hi)[:, 0]).astype(np.int64)

    counts = row_counts


def _midrange_queries(xs):
    x = np.asarray(xs, np.float32)
    n = len(x)
    ts = np.arange(n, dtype=np.float32)
    z = np.zeros(n, np.float32)
    return SegmentArray(x, z, z.copy(), x.copy(), z.copy(), z.copy(), ts,
                        ts + 0.5, np.arange(n, dtype=np.int32),
                        np.zeros(n, np.int32))


@pytest.mark.parametrize("variant", ["min", "max"])
def test_greedy_lane_takes_over_the_next_on_a_free_crossing(variant):
    """A batch that merges free across a change of the singleton price
    makes its lane absorb the next one; the plan stays the merge loop's."""
    rng = np.random.default_rng(21)
    index = TemporalBinIndex.build(random_segments(rng, 200), num_bins=16)
    queries = _midrange_queries(
        [5, 15, 10, 10, 3, 7, 5, 2, 4, 6, 9, 1, 11, 6, 6, 8]
        + rng.integers(0, 12, 48).tolist())
    counter = _MidrangeCounter(queries)
    want = _greedy_reference(index, queries, 3, variant, counter)
    with spans.recording() as rec:
        got = batching.ALGORITHMS[f"greedysetsplit-{variant}"](
            index, queries, 3, counter=counter)
    assert _batches(got) == _batches(want)
    assert rec.counts["plan_scan_restarts"] >= 1


def test_skewed_world_scans_its_lanes_together():
    """On the skewed world every lane ends where its batch does (no
    restart), and the lanes go together: fewer pricing calls than lanes."""
    index, queries, counter = _plan_inputs("skewed", "bin", None)
    with spans.recording() as rec:
        batching.greedysetsplit_min(index, queries, 7, counter=counter)
    c = rec.counts
    assert c["plan_scan_lanes"] >= 10
    assert c["plan_scan_restarts"] == 0
    assert c["plan_price_calls"] < c["plan_scan_lanes"]


@pytest.mark.parametrize("world,level,cap", [("skewed", "bin", None),
                                             ("wide", "box", 2),
                                             ("randwalk", None, None)])
def test_greedy_prices_the_same_in_chunks(monkeypatch, world, level, cap):
    """A round's stack priced in chunks of a few rows gives the plan it
    gives priced whole."""
    index, queries, counter = _plan_inputs(world, level, cap)
    with spans.recording() as whole:
        want = batching.greedysetsplit_max(index, queries, 7, counter=counter)
    monkeypatch.setattr(batching, "SCAN_CHUNK_ROWS", 3)
    with spans.recording() as chunked:
        got = batching.greedysetsplit_max(index, queries, 7, counter=counter)
    assert _batches(got) == _batches(want)
    assert (chunked.counts["plan_price_rows"]
            == whole.counts["plan_price_rows"])
    assert (chunked.counts["plan_price_calls"]
            > whole.counts["plan_price_calls"])


def test_wide_world_prices_prefixes_under_their_own_limits():
    """The ``wide`` case above tells per-row thresholds from a shared one:
    priced in one stack under a shared threshold, some prefix unions of
    its queries get another count than alone."""
    index, queries, counter = _plan_inputs("wide", "bin", None)
    n = len(queries)
    lo = np.minimum.accumulate(counter.qlo)
    hi = np.maximum.accumulate(counter.qhi)
    t0 = np.full(n, float(queries.ts[0]))
    t1 = np.maximum.accumulate(queries.te.astype(np.float64))
    alone = counter.row_counts(t0, t1, lo, hi)
    assert (alone == [counter.counts(t0[k:k + 1], t1[k:k + 1], lo[k:k + 1],
                                     hi[k:k + 1])[0] for k in range(n)]).all()
    assert (counter.counts(t0, t1, lo, hi) != alone).any()


#: sha256 (first 16 hex digits) of each SETSPLIT / PERIODIC plan below,
#: as the planner gave them before GREEDYSETSPLIT got its own scan.
SETSPLIT_PLANS = {
    ('galaxy', None, None, 'periodic'): 'cb3bc32a803ba047',
    ('galaxy', None, None, 'setsplit-fixed'): '6420ca2f03901345',
    ('galaxy', None, None, 'setsplit-max'): '3df5a01d978d5a73',
    ('galaxy', None, None, 'setsplit-minmax'): '6a14a346c5f16f92',
    ('galaxy', 'bin', None, 'periodic'): 'cb3bc32a803ba047',
    ('galaxy', 'bin', None, 'setsplit-fixed'): '6420ca2f03901345',
    ('galaxy', 'bin', None, 'setsplit-max'): '3df5a01d978d5a73',
    ('galaxy', 'bin', None, 'setsplit-minmax'): '6a14a346c5f16f92',
    ('galaxy', 'box', 2, 'periodic'): 'cb3bc32a803ba047',
    ('galaxy', 'box', 2, 'setsplit-fixed'): '6420ca2f03901345',
    ('galaxy', 'box', 2, 'setsplit-max'): '3df5a01d978d5a73',
    ('galaxy', 'box', 2, 'setsplit-minmax'): '6a14a346c5f16f92',
    ('randwalk', None, None, 'periodic'): '5c40e206c6bf8471',
    ('randwalk', None, None, 'setsplit-fixed'): '0d7555f9b51780fd',
    ('randwalk', None, None, 'setsplit-max'): '803a70139e189a1d',
    ('randwalk', None, None, 'setsplit-minmax'): 'eeca9fc2b10287e1',
    ('randwalk', 'bin', None, 'periodic'): '5c40e206c6bf8471',
    ('randwalk', 'bin', None, 'setsplit-fixed'): '0d7555f9b51780fd',
    ('randwalk', 'bin', None, 'setsplit-max'): '803a70139e189a1d',
    ('randwalk', 'bin', None, 'setsplit-minmax'): 'eeca9fc2b10287e1',
    ('randwalk', 'box', 2, 'periodic'): '5c40e206c6bf8471',
    ('randwalk', 'box', 2, 'setsplit-fixed'): 'c7a56fa58dc89d70',
    ('randwalk', 'box', 2, 'setsplit-max'): '63e111d5be83dacc',
    ('randwalk', 'box', 2, 'setsplit-minmax'): '57e2a285811f91d6',
    ('wide', None, None, 'periodic'): 'f2103da4eca938e5',
    ('wide', None, None, 'setsplit-fixed'): 'ef7f528bc4381430',
    ('wide', None, None, 'setsplit-max'): 'bf77310a6277de41',
    ('wide', None, None, 'setsplit-minmax'): 'cf64772e53fc36fe',
    ('wide', 'bin', None, 'periodic'): 'f2103da4eca938e5',
    ('wide', 'bin', None, 'setsplit-fixed'): '9b96693ebdd05618',
    ('wide', 'bin', None, 'setsplit-max'): 'ccfe533429170dc0',
    ('wide', 'bin', None, 'setsplit-minmax'): '99723d5be7f7e078',
    ('wide', 'box', 2, 'periodic'): 'f2103da4eca938e5',
    ('wide', 'box', 2, 'setsplit-fixed'): 'fa4c77c81f43757b',
    ('wide', 'box', 2, 'setsplit-max'): '08c4e6ee8b5befab',
    ('wide', 'box', 2, 'setsplit-minmax'): 'd86839e01f40636d',
}


def _digest(plan):
    rows = [(b.q_first, b.q_last, b.qt0, b.qt1, b.cand_first, b.cand_last,
             b.num_ints) for b in plan.batches]
    return hashlib.sha256(repr((plan.algorithm, plan.params, rows))
                          .encode()).hexdigest()[:16]


SETSPLIT_CASES = [("periodic", {"s": 9}), ("setsplit-fixed", {"num_batches": 12}),
                  ("setsplit-max", {"max_size": 20}),
                  ("setsplit-minmax", {"min_size": 4, "max_size": 20})]


@pytest.mark.parametrize("world", ["galaxy", "randwalk", "wide"])
@pytest.mark.parametrize("level,cap", [(None, None), ("bin", None),
                                       ("box", 2)])
@pytest.mark.parametrize("name,kw", SETSPLIT_CASES)
def test_setsplit_and_periodic_plans_unchanged(world, level, cap, name, kw):
    index, queries, counter = _plan_inputs(world, level, cap)
    plan = batching.ALGORITHMS[name](index, queries, **kw, counter=counter)
    assert _digest(plan) == SETSPLIT_PLANS[(world, level, cap, name)]


def test_plan_counts_its_pricing_calls():
    """``db.query`` leaves the scan's pricing calls and rows on
    ``ExecStats.counts``: a few stacked calls for GALAXY's 41 lanes,
    well under one per phase-1 batch or per query segment."""
    db, queries, d = _galaxy_world()
    pol = repro.ExecutionPolicy(num_bins=48, pruning="spatial")
    tdb = repro.TrajectoryDB.from_segments(db, policy=pol)
    counts = tdb.query(queries, d, backend="jnp").stats.counts
    calls, rows = counts["plan_price_calls"], counts["plan_price_rows"]
    n = len(queries)
    index, _, counter = _plan_inputs("galaxy", "bin", None)
    phase1 = batching.greedysetsplit_min(index, queries, 1,
                                         counter=counter).num_batches
    assert 2 <= calls <= phase1 * int(np.ceil(np.log2(n))) + 2
    assert calls <= n // 4
    assert n + calls - 1 <= rows
