"""Temporal-bin index: the paper's §4 worked example (Fig. 1) + properties."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings
from _hypothesis_compat import strategies as st

from conftest import random_segments
from repro.core.index import TemporalBinIndex
from repro.core.segments import SegmentArray


def _fig1_db() -> SegmentArray:
    """The 14-segment example of Fig. 1: extent [0, 12], 4 bins of width 3.

    Bin B1 holds the segments with t_start in [3, 6): l6, l7, l8 (0-based
    5..7); l8 has the largest t_end at 6.2 ⇒ B1 = (3, 6.2, 5, 7).
    """
    ts = np.array([0.0, 0.5, 1.0, 1.5, 2.0,        # bin 0 (5 segs)
                   3.0, 4.0, 5.0,                  # bin 1 (l6, l7, l8)
                   6.0, 7.0, 8.0,                  # bin 2
                   9.0, 10.0, 10.5], np.float32)   # bin 3
    te = np.array([2.0, 2.5, 2.8, 2.9, 3.5,
                   5.0, 5.5, 6.2,
                   8.0, 8.5, 8.9,
                   11.0, 11.5, 12.0], np.float32)
    n = len(ts)
    z = np.zeros(n, np.float32)
    return SegmentArray(z, z.copy(), z.copy(), z.copy(), z.copy(), z.copy(),
                        ts, te, np.arange(n, dtype=np.int32),
                        np.zeros(n, np.int32))


class TestFig1:
    def test_bin_descriptions(self):
        idx = TemporalBinIndex.build(_fig1_db(), num_bins=4)
        assert idx.bin_width == pytest.approx(3.0)
        # B1: t_start in [3,6) → segments 5..7, B_end = 6.2
        assert idx.b_first[1] == 5 and idx.b_last[1] == 7
        assert idx.b_end[1] == pytest.approx(6.2)
        assert idx.b_first[0] == 0 and idx.b_last[0] == 4
        assert idx.b_end[0] == pytest.approx(3.5)
        assert idx.b_first[3] == 11 and idx.b_last[3] == 13

    def test_query_overlapping_bins(self):
        """Paper §4: query [8, 10] overlaps bins B2 and B3 ⇒ candidates
        l9..l14 (0-based 8..13)."""
        idx = TemporalBinIndex.build(_fig1_db(), num_bins=4)
        first, last = idx.candidate_range(8.0, 10.0)
        assert (first, last) == (8, 13)

    def test_query_before_everything(self):
        idx = TemporalBinIndex.build(_fig1_db(), num_bins=4)
        assert idx.candidate_range(-5.0, -1.0) == (0, -1)

    def test_num_interactions(self):
        idx = TemporalBinIndex.build(_fig1_db(), num_bins=4)
        assert idx.num_interactions(8.0, 10.0, batch_size=10) == 60


class TestProperties:
    def test_requires_sorted(self):
        db = _fig1_db().take(np.array([3, 1, 0]))
        with pytest.raises(ValueError):
            TemporalBinIndex.build(db, num_bins=4)

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10_000), num_bins=st.integers(1, 200),
           n=st.integers(1, 300))
    def test_candidate_range_is_superset(self, seed, num_bins, n):
        """Every temporally overlapping segment is inside the candidate
        range (the index may over-approximate, never under)."""
        rng = np.random.default_rng(seed)
        db = random_segments(rng, n)
        idx = TemporalBinIndex.build(db, num_bins=num_bins)
        qt0, qt1 = sorted(rng.uniform(-5, 60, 2))
        first, last = idx.candidate_range(qt0, qt1)
        overlap = np.nonzero((db.ts <= qt1) & (db.te >= qt0))[0]
        if overlap.size:
            assert first <= overlap.min() and last >= overlap.max()

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000), n=st.integers(2, 200))
    def test_batch_matches_scalar(self, seed, n):
        rng = np.random.default_rng(seed)
        db = random_segments(rng, n)
        idx = TemporalBinIndex.build(db, num_bins=50)
        qt0s = rng.uniform(-5, 55, 20)
        qt1s = qt0s + rng.uniform(0, 10, 20)
        firsts, lasts = idx.candidate_range_batch(qt0s, qt1s)
        for i in range(20):
            assert (firsts[i], lasts[i]) == idx.candidate_range(
                float(qt0s[i]), float(qt1s[i]))

    def test_bins_partition_segments(self):
        rng = np.random.default_rng(0)
        db = random_segments(rng, 500)
        idx = TemporalBinIndex.build(db, num_bins=64)
        nonempty = idx.b_last >= idx.b_first
        total = int((idx.b_last[nonempty] - idx.b_first[nonempty] + 1).sum())
        assert total == len(db)


class TestSpatialMBRs:
    """The per-bin MBR layer (PR 5): containment, prefix/suffix unions,
    and the coarse pricing estimate's conservatism."""

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000), num_bins=st.sampled_from([4, 33, 128]))
    def test_bin_mbrs_contain_member_segments(self, seed, num_bins):
        rng = np.random.default_rng(seed)
        db = random_segments(rng, 200)
        idx = TemporalBinIndex.build(db, num_bins=num_bins)
        slo, shi = db.mbrs()
        for j in range(num_bins):
            f, l = int(idx.b_first[j]), int(idx.b_last[j])
            if l < f:
                assert np.all(np.isinf(idx.mbr_lo[j]))
                continue
            assert np.all(idx.mbr_lo[j] <= slo[f:l + 1].min(axis=0) + 1e-6)
            assert np.all(idx.mbr_hi[j] >= shi[f:l + 1].max(axis=0) - 1e-6)

    def test_prefix_suffix_are_running_unions(self):
        rng = np.random.default_rng(1)
        db = random_segments(rng, 300)
        idx = TemporalBinIndex.build(db, num_bins=32)
        want_lo = np.minimum.accumulate(idx.mbr_lo, axis=0)
        np.testing.assert_array_equal(idx.prefix_lo, want_lo)
        want_suf = np.maximum.accumulate(idx.mbr_hi[::-1], axis=0)[::-1]
        np.testing.assert_array_equal(idx.suffix_hi, want_suf)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000), d=st.floats(0.5, 10.0))
    def test_coarse_estimate_is_conservative(self, seed, d):
        """The coarse pricing count never under-counts the exact pruned
        candidates, and never exceeds the temporal-only count."""
        rng = np.random.default_rng(seed)
        db = random_segments(rng, 300)
        queries = random_segments(rng, 16)
        idx = TemporalBinIndex.build(db, num_bins=100)
        qlo, qhi = queries.mbrs()
        qt0 = queries.ts.astype(np.float64)
        qt1 = queries.te.astype(np.float64)
        est = idx.estimate_pruned_candidates_batch(qt0, qt1, qlo, qhi,
                                                   float(d))
        temporal = idx.num_candidates_batch(qt0, qt1)
        for k in range(len(queries)):
            exact = idx.pruned_num_candidates(float(qt0[k]), float(qt1[k]),
                                              qlo[k], qhi[k], float(d))
            assert exact <= est[k] <= temporal[k]

    def test_estimate_equals_temporal_when_nothing_prunes(self):
        """With a huge d the estimate reduces exactly to the temporal
        count — pruning-aware pricing is a strict refinement."""
        rng = np.random.default_rng(2)
        db = random_segments(rng, 250)
        queries = random_segments(rng, 20)
        idx = TemporalBinIndex.build(db, num_bins=64)
        qlo, qhi = queries.mbrs()
        est = idx.estimate_pruned_candidates_batch(
            queries.ts, queries.te, qlo, qhi, 1e9)
        np.testing.assert_array_equal(
            est, idx.num_candidates_batch(queries.ts, queries.te))

    @pytest.mark.filterwarnings("ignore:invalid value:RuntimeWarning")
    @pytest.mark.parametrize("level,cap", [("bin", None), ("box", 3)])
    def test_row_estimate_is_each_row_alone(self, level, cap):
        """``estimate_pruned_candidates_rows`` prices each row as a one-row
        stack does: its threshold follows its own scale, floored at the
        database's, from its finite coordinates only."""
        rng = np.random.default_rng(4)
        db = random_segments(rng, 300)
        idx = TemporalBinIndex.build(db, num_bins=64, kboxes=4)
        queries = random_segments(rng, 40, box=45.0)
        qlo, qhi = queries.mbrs()
        qlo[::5] *= 80.0                  # scales above the database's
        qhi[::5] *= 80.0
        qlo[1::5] *= 0.01                 # and below it
        qhi[1::5] *= 0.01
        qlo[2, 1], qhi[3, 2] = -np.inf, np.inf   # (inf − inf in the gaps)
        qt0 = queries.ts.astype(np.float64)
        qt1 = qt0 + 20.0
        d = 0.5
        limits = idx._row_limits(d, qlo, qhi)
        rows = idx.estimate_pruned_candidates_rows(
            qt0, qt1, qlo, qhi, d, level=level, max_subranges=cap)
        for k in range(len(queries)):
            one = slice(k, k + 1)
            assert limits[k] == idx._limit(d, qlo[one], qhi[one])
            assert rows[k] == idx.estimate_pruned_candidates_batch(
                qt0[one], qt1[one], qlo[one], qhi[one], d, level=level,
                max_subranges=cap)[0]
        assert len(set(limits.tolist())) > 2
        shared = idx.estimate_pruned_candidates_batch(
            qt0, qt1, qlo, qhi, d, level=level, max_subranges=cap)
        assert (shared != rows).any()

    def test_subranges_subset_of_candidate_range(self):
        rng = np.random.default_rng(3)
        db = random_segments(rng, 400)
        queries = random_segments(rng, 10)
        idx = TemporalBinIndex.build(db, num_bins=50)
        qlo, qhi = queries.mbrs()
        for k in range(len(queries)):
            qt0, qt1 = float(queries.ts[k]), float(queries.te[k])
            first, last = idx.candidate_range(qt0, qt1)
            for f, l in idx.candidate_subranges(qt0, qt1, qlo[k], qhi[k],
                                                3.0):
                assert first <= f <= l <= last

    def test_max_subranges_cap_merges_smallest_gaps(self):
        rng = np.random.default_rng(4)
        db = random_segments(rng, 400)
        idx = TemporalBinIndex.build(db, num_bins=200)
        qlo, qhi = db.mbrs()
        lo, hi = qlo.min(axis=0), qhi.max(axis=0)
        subs = idx.candidate_subranges(0.0, 60.0, lo, hi, 0.5,
                                       max_subranges=2)
        assert len(subs) <= 2
        uncapped = idx.candidate_subranges(0.0, 60.0, lo, hi, 0.5,
                                           max_subranges=10**9)
        # the capped ranges cover everything the uncapped ones do
        def covered(ranges, i):
            return any(f <= i <= l for f, l in ranges)
        for f, l in uncapped:
            assert covered(subs, f) and covered(subs, l)
