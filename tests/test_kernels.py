"""Per-kernel allclose sweeps: Pallas kernels vs pure-jnp oracles."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings
from _hypothesis_compat import strategies as st

from conftest import random_segments
from repro.kernels import ops, ref
from repro.kernels.distthresh import distthresh_pallas


class TestDistThreshKernel:
    @pytest.mark.parametrize("c,q,cblk,qblk", [
        (16, 16, 8, 8), (32, 8, 16, 8), (8, 64, 8, 32), (128, 128, 64, 64),
    ])
    @pytest.mark.parametrize("dtype", [np.float32])
    def test_matches_oracle_shapes(self, c, q, cblk, qblk, dtype):
        rng = np.random.default_rng(c * 1000 + q)
        entries = random_segments(rng, c).packed().astype(dtype)
        queries = random_segments(rng, q).packed().astype(dtype)
        d = np.float32(3.0)
        te_p, tx_p, hit_p = distthresh_pallas(
            entries, queries.T, d, cand_blk=cblk, qry_blk=qblk)
        te_r, tx_r, hit_r = ref.interaction_tile(entries, queries, d)
        np.testing.assert_array_equal(np.asarray(hit_p).astype(bool),
                                      np.asarray(hit_r))
        # f32 root-solve: interval endpoints agree to ~1e-5 relative
        np.testing.assert_allclose(np.asarray(te_p), np.asarray(te_r),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(tx_p), np.asarray(tx_r),
                                   rtol=1e-4, atol=1e-3)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000),
           d=st.floats(0.1, 20.0))
    def test_matches_oracle_random(self, seed, d):
        rng = np.random.default_rng(seed)
        entries = random_segments(rng, 24).packed()
        queries = random_segments(rng, 16).packed()
        te_p, tx_p, hit_p = distthresh_pallas(
            entries, queries.T, np.float32(d), cand_blk=8, qry_blk=8)
        te_r, tx_r, hit_r = ref.interaction_tile(entries, queries,
                                                 np.float32(d))
        np.testing.assert_array_equal(np.asarray(hit_p).astype(bool),
                                      np.asarray(hit_r))
        np.testing.assert_allclose(np.asarray(te_p), np.asarray(te_r),
                                   rtol=1e-4, atol=1e-3)

    def test_analytic_head_on_approach(self):
        """Two points approaching head-on, both at unit speed: separation
        |10 − 2t| ≤ d=2 ⇒ interval [4, 6] around the meeting at t=5."""
        entries = np.array([[0, 0, 0, 10, 0, 0, 0, 10]], np.float32)
        queries = np.array([[10, 0, 0, 0, 0, 0, 0, 10]], np.float32)
        d = np.float32(2.0)
        te, tx, hit = ref.interaction_tile(entries, queries, d)
        assert bool(hit[0, 0])
        assert float(te[0, 0]) == pytest.approx(4.0, abs=1e-5)
        assert float(tx[0, 0]) == pytest.approx(6.0, abs=1e-5)

    def test_parallel_motion_never_within(self):
        entries = np.array([[0, 0, 0, 10, 0, 0, 0, 10]], np.float32)
        queries = np.array([[0, 5, 0, 10, 5, 0, 0, 10]], np.float32)
        _, _, hit = ref.interaction_tile(entries, queries, np.float32(2.0))
        assert not bool(hit[0, 0])

    def test_parallel_motion_always_within(self):
        entries = np.array([[0, 0, 0, 10, 0, 0, 0, 10]], np.float32)
        queries = np.array([[0, 1, 0, 10, 1, 0, 0, 10]], np.float32)
        te, tx, hit = ref.interaction_tile(entries, queries, np.float32(2.0))
        assert bool(hit[0, 0])
        assert float(te[0, 0]) == pytest.approx(0.0, abs=1e-5)
        assert float(tx[0, 0]) == pytest.approx(10.0, abs=1e-5)

    def test_temporal_miss(self):
        entries = np.array([[0, 0, 0, 1, 0, 0, 0, 1]], np.float32)
        queries = np.array([[0, 0, 0, 1, 0, 0, 5, 6]], np.float32)
        _, _, hit = ref.interaction_tile(entries, queries, np.float32(100.0))
        assert not bool(hit[0, 0])

    def test_classes_partition(self):
        rng = np.random.default_rng(7)
        entries = random_segments(rng, 40).packed()
        queries = random_segments(rng, 30).packed()
        a, b, g = ref.interaction_classes(entries, queries, np.float32(3.0))
        total = (np.asarray(a).astype(int) + np.asarray(b).astype(int)
                 + np.asarray(g).astype(int))
        np.testing.assert_array_equal(total, np.ones_like(total))


class TestFusedCompaction:
    """In-kernel compaction (distthresh_compact_pallas) vs the dense path."""

    @pytest.mark.parametrize("c,q,cblk,qblk", [
        (16, 16, 16, 16),      # single tile
        (40, 24, 16, 8),       # multi-tile + row padding both axes
        (8, 64, 8, 16),        # query-tile streaming
    ])
    def test_matches_dense_hit_set(self, c, q, cblk, qblk):
        rng = np.random.default_rng(c * 100 + q)
        entries = random_segments(rng, c).packed()
        queries = random_segments(rng, q).packed()
        d = np.float32(15.0)
        fused = ops.query_block(entries, queries, d, capacity=4096,
                                use_pallas=True, compaction="fused",
                                cand_blk=cblk, qry_blk=qblk)
        dense = ops.query_block(entries, queries, d, capacity=4096,
                                use_pallas=True, compaction="dense",
                                cand_blk=cblk, qry_blk=qblk)
        nf, nd = int(fused["count"]), int(dense["count"])
        assert nf == nd
        assert nf > 0, "fixture produced no hits — adjust d"

        def canon(out, n):
            e = np.asarray(out["entry_idx"][:n])
            qi = np.asarray(out["query_idx"][:n])
            order = np.lexsort((qi, e))
            return (e[order], qi[order],
                    np.asarray(out["t_enter"][:n])[order],
                    np.asarray(out["t_exit"][:n])[order])

        fe, fq, fen, fex = canon(fused, nf)
        de, dq, den, dex = canon(dense, nd)
        np.testing.assert_array_equal(fe, de)
        np.testing.assert_array_equal(fq, dq)
        # fused recomputes intervals on the gathered hit segments; dense
        # keeps the dense tile's — identical up to f32 fusion order
        np.testing.assert_allclose(fen, den, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(fex, dex, rtol=1e-4, atol=1e-3)
        # pad slots beyond the count are -1 on both paths
        assert np.all(np.asarray(fused["entry_idx"][nf:]) == -1)
        assert np.all(np.asarray(fused["query_idx"][nf:]) == -1)

    def test_tile_order_deterministic(self):
        rng = np.random.default_rng(5)
        entries = random_segments(rng, 32).packed()
        queries = random_segments(rng, 32).packed()
        a = ops.query_block(entries, queries, np.float32(8.0), capacity=2048,
                            use_pallas=True, compaction="fused",
                            cand_blk=8, qry_blk=8)
        b = ops.query_block(entries, queries, np.float32(8.0), capacity=2048,
                            use_pallas=True, compaction="fused",
                            cand_blk=8, qry_blk=8)
        np.testing.assert_array_equal(np.asarray(a["entry_idx"]),
                                      np.asarray(b["entry_idx"]))
        np.testing.assert_array_equal(np.asarray(a["query_idx"]),
                                      np.asarray(b["query_idx"]))

    def test_overflow_exact_count_no_dense_pass(self):
        """The fused kernel reports the exact total even when the buffer
        overflows — sizing a retry needs no second (dense) counting pass."""
        rng = np.random.default_rng(9)
        entries = random_segments(rng, 48).packed()
        queries = random_segments(rng, 32).packed()
        d = np.float32(50.0)                       # everything hits
        truth = int(np.asarray(ref.count_hits(entries, queries, d)))
        out = ops.query_block(entries, queries, d, capacity=16,
                              use_pallas=True, compaction="fused",
                              cand_blk=16, qry_blk=16)
        assert int(out["count"]) == truth > 16
        # retry at the exact-count bucket recovers everything
        out2 = ops.query_block(entries, queries, d, capacity=2048,
                               use_pallas=True, compaction="fused",
                               cand_blk=16, qry_blk=16)
        assert int(out2["count"]) == truth
        assert np.all(np.asarray(out2["entry_idx"][:truth]) >= 0)

    def test_unknown_compaction_raises(self):
        rng = np.random.default_rng(1)
        entries = random_segments(rng, 8).packed()
        queries = random_segments(rng, 8).packed()
        with pytest.raises(ValueError, match="compaction"):
            ops.query_block(entries, queries, np.float32(1.0), capacity=64,
                            compaction="atomic")


class TestInterpretResolution:
    def test_interpret_resolves_true_on_cpu(self):
        """With no explicit choice, Pallas interprets on the CPU (the test
        platform) — and an explicit value always wins."""
        import jax

        import repro
        from repro.kernels.distthresh import resolve_interpret
        assert jax.devices()[0].platform == "cpu"
        assert resolve_interpret() is True
        assert resolve_interpret(None, jax.devices()[0]) is True
        assert resolve_interpret(False) is False
        rng = np.random.default_rng(37)
        db = repro.TrajectoryDB.from_segments(random_segments(rng, 32))
        assert db.policy.interpret is None
        assert db.engine("pallas").interpret is True


class TestEmptyInputGuards:
    """Zero-row entries/queries are reachable by direct kernel users; the
    pad-time computation (jnp.max over temporal extents) must not see
    them."""

    @pytest.mark.parametrize("c,q", [(0, 8), (8, 0), (0, 0)])
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_interaction_tiles_empty(self, c, q, use_pallas):
        rng = np.random.default_rng(3)
        entries = random_segments(rng, c).packed() if c else np.zeros((0, 8), np.float32)
        queries = random_segments(rng, q).packed() if q else np.zeros((0, 8), np.float32)
        te, tx, hit = ops.interaction_tiles(entries, queries, np.float32(2.0),
                                            use_pallas=use_pallas)
        assert te.shape == tx.shape == hit.shape == (c, q)
        assert not np.asarray(hit).any()

    @pytest.mark.parametrize("compaction", ["fused", "dense"])
    def test_query_block_empty(self, compaction):
        entries = np.zeros((0, 8), np.float32)
        rng = np.random.default_rng(4)
        queries = random_segments(rng, 8).packed()
        out = ops.query_block(entries, queries, np.float32(2.0), capacity=64,
                              use_pallas=True, compaction=compaction)
        assert int(out["count"]) == 0
        assert np.all(np.asarray(out["entry_idx"]) == -1)


class TestQueryBlockCompaction:
    def test_counts_and_order(self):
        rng = np.random.default_rng(11)
        entries = random_segments(rng, 32).packed()
        queries = random_segments(rng, 16).packed()
        d = np.float32(5.0)
        out = ops.query_block(entries, queries, d, capacity=4096,
                              use_pallas=False)
        _, _, hit = ref.interaction_tile(entries, queries, d)
        hit = np.asarray(hit)
        count = int(out["count"])
        assert count == hit.sum()
        ei, qi = np.nonzero(hit)                      # row-major ground truth
        np.testing.assert_array_equal(np.asarray(out["entry_idx"][:count]), ei)
        np.testing.assert_array_equal(np.asarray(out["query_idx"][:count]), qi)
        assert np.all(np.asarray(out["entry_idx"][count:]) == -1)

    def test_overflow_reports_true_count(self):
        rng = np.random.default_rng(12)
        entries = random_segments(rng, 32).packed()
        queries = random_segments(rng, 16).packed()
        d = np.float32(50.0)                          # everything hits
        out = ops.query_block(entries, queries, d, capacity=8,
                              use_pallas=False)
        assert int(out["count"]) > 8                 # caller must retry

    def test_lowering_error_reaches_caller_unchanged(self, monkeypatch):
        """A kernel that fails to lower raises to the caller as-is: no
        other compaction strategy is tried in its place."""
        from repro.kernels import distthresh as dt
        err = RuntimeError("Mosaic failed to compile TPU kernel")
        calls = []

        def refuses_to_lower(*args, **kwargs):
            calls.append(kwargs)
            raise err

        monkeypatch.setattr(dt, "distthresh_compact_pallas",
                            refuses_to_lower)
        rng = np.random.default_rng(23)
        # Unseen shapes, so the monkeypatched callable is actually traced.
        entries = random_segments(rng, 72).packed()
        queries = random_segments(rng, 24).packed()
        with pytest.raises(RuntimeError) as info:
            ops.query_block(entries, queries, np.float32(15.0),
                            capacity=1024, use_pallas=True,
                            compaction="fused", cand_blk=8, qry_blk=8)
        assert info.value is err
        assert len(calls) == 1
