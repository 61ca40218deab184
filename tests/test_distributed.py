"""Distributed query engine + sharding rules.

The multi-device tests run in a subprocess with a forced 8-device host
platform (the main test process must keep seeing 1 device — see conftest).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.distributed import (choose_sharding, route_query_to_pods,
                                    temporal_pod_partition)
from repro.core.segments import SegmentArray

from conftest import random_segments


class TestPodPartition:
    def test_slices_cover_everything(self):
        rng = np.random.default_rng(0)
        db = random_segments(rng, 500)
        for pods in (2, 3, 8):
            slices = temporal_pod_partition(db, pods)
            covered = sorted(i for f, l in slices for i in range(f, l + 1))
            assert covered == list(range(len(db)))

    def test_each_segment_owned_once(self):
        rng = np.random.default_rng(1)
        db = random_segments(rng, 300)
        slices = temporal_pod_partition(db, 4)
        seen = []
        for f, l in slices:
            seen.extend(range(f, l + 1))
        assert len(seen) == len(set(seen)) == len(db)


class TestPodPartitionEdgeCases:
    """Satellite regressions: degenerate inputs must yield valid (possibly
    empty) pod slices, never nonsense ranges."""

    def test_more_pods_than_distinct_time_slices(self):
        rng = np.random.default_rng(2)
        db = random_segments(rng, 12, t_span=(5.0, 5.0))   # one instant
        for pods in (2, 4, 50):
            slices = temporal_pod_partition(db, pods)
            assert len(slices) == pods
            covered = [i for f, l in slices for i in range(f, l + 1)]
            assert sorted(covered) == list(range(12))
            assert len(covered) == len(set(covered))       # owned once
            for f, l in slices:
                assert f >= 0 and l >= f - 1               # valid range

    def test_more_pods_than_segments(self):
        rng = np.random.default_rng(3)
        db = random_segments(rng, 3)
        slices = temporal_pod_partition(db, 16)
        covered = [i for f, l in slices for i in range(f, l + 1)]
        assert sorted(covered) == [0, 1, 2]
        # at least 13 of the 16 pods must be (validly) empty
        assert sum(1 for f, l in slices if l < f) >= 13

    def test_empty_database(self):
        empty = SegmentArray.empty()
        assert temporal_pod_partition(empty, 4) == [(0, -1)] * 4
        assert route_query_to_pods(0.0, 1.0, empty, [(0, -1)] * 4) == []

    def test_invalid_num_pods(self):
        rng = np.random.default_rng(4)
        db = random_segments(rng, 10)
        with pytest.raises(ValueError, match="num_pods"):
            temporal_pod_partition(db, 0)

    def test_empty_query_extent_routes_nowhere(self):
        rng = np.random.default_rng(5)
        db = random_segments(rng, 50)
        slices = temporal_pod_partition(db, 4)
        assert route_query_to_pods(10.0, 5.0, db, slices) == []

    def test_halo_slices_superset_of_owned(self):
        rng = np.random.default_rng(6)
        db = random_segments(rng, 400)
        owned = temporal_pod_partition(db, 4)
        halo = temporal_pod_partition(db, 4, halo=True)
        edges = np.linspace(float(db.ts[0]), float(db.ts[-1]), 5)
        widened = 0
        for p, ((of, ol), (hf, hl)) in enumerate(zip(owned, halo)):
            assert hf <= of and hl == ol                   # widened left only
            widened += of - hf
            # every excluded earlier segment really ends before the window
            if hf > 0:
                assert float(np.max(db.te[:hf])) < edges[p]
        assert widened > 0, "fixture produced no boundary-crossing segments"


class TestPodPartitionBalance:
    """Satellite: balance="num_ints" equalizes per-pod interaction load on
    temporally skewed databases via the batching algorithms' prefix-sum
    machinery; the default balance="time" is unchanged."""

    @staticmethod
    def _skewed_db(rng, n=500, dense_frac=0.8, dense_span=(0.0, 5.0),
                   full_span=(0.0, 50.0)):
        """dense_frac of the segments packed into 10% of the time range."""
        n_dense = int(n * dense_frac)
        ts = np.concatenate([
            rng.uniform(*dense_span, n_dense),
            rng.uniform(dense_span[1], full_span[1], n - n_dense),
        ]).astype(np.float32)
        te = ts + rng.uniform(0.1, 1.0, n).astype(np.float32)
        order = np.argsort(ts, kind="stable")
        p = rng.uniform(0, 30, (n, 3)).astype(np.float32)
        return SegmentArray(
            xs=p[order, 0], ys=p[order, 1], zs=p[order, 2],
            xe=p[order, 0] + 1, ye=p[order, 1] + 1, ze=p[order, 2] + 1,
            ts=ts[order], te=te[order],
            seg_id=np.arange(n, dtype=np.int32),
            traj_id=np.zeros(n, np.int32))

    @staticmethod
    def _pod_interactions(db, queries, slices):
        """Per-pod interaction load: candidate rows each pod evaluates for
        the query stream (its owned segments temporally overlapping each
        query)."""
        loads = []
        for first, last in slices:
            if last < first:
                loads.append(0)
                continue
            ets = db.ts[first:last + 1]
            ete = db.te[first:last + 1]
            # segment overlaps query iff e.ts <= q.te and e.te >= q.ts
            loads.append(int(sum(
                np.count_nonzero((ets <= qte) & (ete >= qts))
                for qts, qte in zip(queries.ts, queries.te))))
        return np.asarray(loads)

    def test_num_ints_balance_beats_time_on_skew(self):
        rng = np.random.default_rng(40)
        db = self._skewed_db(rng)
        # the query workload follows the data skew (the paper draws query
        # trajectories from the same scenario distribution, §7.2)
        queries = self._skewed_db(rng, n=64)
        by_time = temporal_pod_partition(db, 4)
        by_load = temporal_pod_partition(db, 4, balance="num_ints")
        lt = self._pod_interactions(db, queries, by_time)
        ll = self._pod_interactions(db, queries, by_load)
        # same total work, different distribution
        assert lt.sum() == ll.sum() > 0
        ratio_time = lt.max() / lt.mean()
        ratio_load = ll.max() / ll.mean()
        # acceptance: >= 2x better max/mean interaction balance
        assert ratio_time >= 2.0 * ratio_load, (ratio_time, ratio_load)

    def test_num_ints_is_a_valid_partition(self):
        rng = np.random.default_rng(41)
        db = self._skewed_db(rng, n=307)
        for pods in (2, 4, 16):
            slices = temporal_pod_partition(db, pods, balance="num_ints")
            covered = [i for f, l in slices for i in range(f, l + 1)]
            assert sorted(covered) == list(range(len(db)))
            assert len(covered) == len(set(covered))
        # degenerate inputs behave like the time balance
        assert temporal_pod_partition(SegmentArray.empty(), 3,
                                      balance="num_ints") == [(0, -1)] * 3
        tiny = random_segments(np.random.default_rng(5), 3)
        slices = temporal_pod_partition(tiny, 16, balance="num_ints")
        assert sorted(i for f, l in slices
                      for i in range(f, l + 1)) == [0, 1, 2]

    def test_num_ints_halo_superset(self):
        rng = np.random.default_rng(42)
        db = self._skewed_db(rng)
        owned = temporal_pod_partition(db, 4, balance="num_ints")
        halo = temporal_pod_partition(db, 4, halo=True, balance="num_ints")
        for (of, ol), (hf, hl) in zip(owned, halo):
            assert hf <= of and hl == ol
            if hf > 0:
                # every excluded earlier segment ends before the window
                assert float(np.max(db.te[:hf])) < float(db.ts[of])

    def test_unknown_balance_raises(self):
        db = random_segments(np.random.default_rng(6), 10)
        with pytest.raises(ValueError, match="balance"):
            temporal_pod_partition(db, 2, balance="weights")

    def test_sharded_engine_accepts_balance(self):
        """backend-level plumbing: a num_ints-balanced ShardedEngine stays
        exact (facade: ExecutionPolicy.shard_balance)."""
        from repro.api import ExecutionPolicy, TrajectoryDB
        rng = np.random.default_rng(43)
        db = self._skewed_db(rng, n=400)
        queries = random_segments(rng, 48)
        tdb = TrajectoryDB.from_segments(
            db, policy=ExecutionPolicy(num_bins=64))
        base = tdb.query(queries, 4.0, backend="jnp")
        pol = tdb.policy.with_(shard_balance="num_ints")
        res = tdb.query(queries, 4.0, backend="shard", policy=pol)
        assert len(res) == len(base)
        np.testing.assert_array_equal(res.entry_idx, base.entry_idx)
        np.testing.assert_array_equal(res.query_idx, base.query_idx)
        assert tdb.backend("shard", pol).engine.balance == "num_ints"
        # distinct policy knob -> distinct cached engine
        assert tdb.backend("shard", pol) is not tdb.backend("shard")


class TestChooseSharding:
    def test_aspect_ratio(self):
        assert choose_sharding(100_000, 64, 16, 16) == "candidates"
        assert choose_sharding(64, 100_000, 16, 16) == "queries"


class TestShardedEngineSingleDevice:
    """backend="shard" correctness on whatever mesh the test process has
    (1 CPU device here; the 8-device path runs in the subprocess below)."""

    @pytest.fixture(scope="class")
    def world(self):
        rng = np.random.default_rng(11)
        db = random_segments(rng, 900)
        queries = random_segments(rng, 100)
        d = 4.0
        from repro.core.engine import brute_force
        return db, queries, d, brute_force(db, queries, d)

    def test_matches_bruteforce_o1_syncs(self, world):
        from repro.core import batching
        from repro.core.distributed import ShardedEngine
        from repro.core.engine import DistanceThresholdEngine
        db, queries, d, bf = world
        eng = DistanceThresholdEngine(db, num_bins=64)
        se = ShardedEngine(db, capacity_per_shard=4096)
        plan = batching.periodic(eng.index, queries, 16)
        rs, stats = se.execute(queries, d, plan)
        rs = rs.sorted_canonical()
        assert len(rs) == len(bf)
        np.testing.assert_array_equal(rs.entry_idx, bf.entry_idx)
        np.testing.assert_array_equal(rs.query_idx, bf.query_idx)
        np.testing.assert_allclose(rs.t_enter, bf.t_enter, rtol=1e-4,
                                   atol=1e-3)
        assert stats.pipelined and stats.num_syncs <= 2

    def test_overflow_retry_stays_o1(self, world):
        from repro.core import batching
        from repro.core.distributed import ShardedEngine
        from repro.core.engine import DistanceThresholdEngine, brute_force
        db, queries, _, _ = world
        d_all = 20.0
        bf = brute_force(db, queries, d_all)
        eng = DistanceThresholdEngine(db, num_bins=64)
        se = ShardedEngine(db, capacity_per_shard=256)
        plan = batching.periodic(eng.index, queries, 64)
        rs, stats = se.execute(queries, d_all, plan)
        rs = rs.sorted_canonical()
        assert len(rs) == len(bf)
        np.testing.assert_array_equal(rs.entry_idx, bf.entry_idx)
        assert stats.total_retries >= 1
        assert stats.num_syncs <= 2                        # still O(1)

    def test_query_beyond_database_extent_no_phantom_hits(self):
        """Regression: shard pre-padding must place pad rows beyond the
        QUERY extent too — a query outlasting the database must not hit
        entry pad rows (which would index past the database).

        The query below is a static point near the origin (where pad rows'
        zero coordinates live) whose extent starts inside the database
        range (so the batch has candidates and *is* dispatched) and ends
        past ``db.te.max() + 1`` — the exact instant database-extent-only
        padding would have placed the pad rows at.
        """
        from repro.core import batching
        from repro.core.distributed import ShardedEngine
        from repro.core.engine import DistanceThresholdEngine, brute_force
        rng = np.random.default_rng(31)
        db = random_segments(rng, 300, t_span=(0.0, 10.0))
        half = np.full(2, 0.5, np.float32)
        queries = SegmentArray(
            xs=half.copy(), ys=half.copy(), zs=half.copy(),
            xe=half.copy(), ye=half.copy(), ze=half.copy(),
            ts=np.array([5.0, 6.0], np.float32),
            te=np.array([float(db.te.max()) + 10.0] * 2, np.float32),
            seg_id=np.arange(2, dtype=np.int32),
            traj_id=np.zeros(2, np.int32))
        d = 5.0
        bf = brute_force(db, queries, d)
        eng = DistanceThresholdEngine(db, num_bins=32)
        se = ShardedEngine(db, capacity_per_shard=4096)
        plan = batching.periodic(eng.index, queries, 2)
        assert plan.batches[0].num_candidates > 0      # really dispatched
        disp = se.dispatcher(queries.packed(), d)
        assert disp._pad_e > float(queries.te.max())   # pads beyond queries
        rs, _ = se.execute(queries, d, plan)
        rs = rs.sorted_canonical()
        assert np.all(rs.entry_idx < len(db))          # no phantom rows
        assert len(rs) == len(bf)
        np.testing.assert_array_equal(rs.entry_idx, bf.entry_idx)
        np.testing.assert_array_equal(rs.query_idx, bf.query_idx)

    def test_sync_mode_matches(self, world):
        from repro.core import batching
        from repro.core.distributed import ShardedEngine
        from repro.core.engine import DistanceThresholdEngine
        db, queries, d, bf = world
        eng = DistanceThresholdEngine(db, num_bins=64)
        se = ShardedEngine(db, capacity_per_shard=4096, pipeline=False)
        plan = batching.periodic(eng.index, queries, 32)
        rs, stats = se.execute(queries, d, plan)
        assert not stats.pipelined
        assert len(rs.sorted_canonical()) == len(bf)

    def test_overflow_redispatch_reuses_prepared_inputs(self, world):
        """Overflow retries re-launch with the prepared per-pod blocks from
        Dispatch.ctx instead of rebuilding/re-slicing them."""
        from repro.core import batching
        from repro.core.distributed import ShardedEngine
        from repro.core.engine import DistanceThresholdEngine
        db, queries, _, _ = world
        eng = DistanceThresholdEngine(db, num_bins=64)
        se = ShardedEngine(db, capacity_per_shard=256)
        plan = batching.periodic(eng.index, queries, 64)
        disp = se.dispatcher(queries.packed(), 20.0)
        builds = []
        orig_launch = disp._launch

        def counting_launch(batch, capacity, prepared):
            builds.append((id(prepared), capacity))
            return orig_launch(batch, capacity, prepared)

        disp._launch = counting_launch
        from repro.core.executor import PipelinedExecutor
        from repro.core.planner import as_query_plan
        rs, stats = PipelinedExecutor(disp).run(
            as_query_plan(plan, default_capacity=256))
        assert stats.total_retries >= 1
        # every retry reused an already-built prepared tuple (same id)
        first_ids = {pid for pid, _ in builds}
        assert len(first_ids) < len(builds)

    def test_facade_backend_shard(self, world):
        from repro.api import ExecutionPolicy, TrajectoryDB
        db, queries, d, bf = world
        tdb = TrajectoryDB.from_segments(
            db, policy=ExecutionPolicy(num_bins=64))
        res = tdb.query(queries, d, backend="shard")
        base = tdb.query(queries, d, backend="jnp")
        assert len(res) == len(base) == len(bf)
        np.testing.assert_array_equal(res.entry_idx, base.entry_idx)
        np.testing.assert_array_equal(res.query_idx, base.query_idx)
        assert res.stats is not None and res.stats.num_syncs <= 2
        # unsorted queries come back in caller order, like every backend
        rng = np.random.default_rng(13)
        perm = rng.permutation(len(queries))
        got = tdb.query(queries.take(perm), d, backend="shard")
        inv = np.empty_like(perm)
        inv[perm] = np.arange(len(perm))
        expect_q = inv[base.query_idx]
        rank = np.lexsort((base.entry_idx, expect_q))
        np.testing.assert_array_equal(got.query_idx, expect_q[rank])
        np.testing.assert_array_equal(got.entry_idx, base.entry_idx[rank])


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    import jax.numpy as jnp
    from repro.core.engine import brute_force
    from repro.core.distributed import DistributedEngine, make_sharded_count_fn
    from repro.data import trajgen

    from repro.launch.mesh import make_mesh_compat
    mesh = make_mesh_compat((4, 2), ("data", "model"))
    db, queries, d = trajgen.make_scenario("S3", scale=0.005)
    bf = brute_force(db, queries, d)
    eng = DistributedEngine(mesh, db, cand_axes=("data",), num_bins=200,
                            capacity_per_shard=8192)
    out = eng.query_batch(queries.packed(), float(queries.ts.min()),
                          float(queries.te.max()), d)
    order = np.lexsort((out["query_idx"], out["entry_idx"]))
    assert out["entry_idx"].shape[0] == len(bf), (out["entry_idx"].shape, len(bf))
    assert np.array_equal(out["entry_idx"][order], bf.entry_idx)
    assert np.allclose(out["t_enter"][order], bf.t_enter, atol=1e-4)
    print("DISTRIBUTED_OK", len(bf))
""")


@pytest.mark.slow
def test_sharded_query_matches_bruteforce_subprocess():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DISTRIBUTED_OK" in proc.stdout


_SHARD_BACKEND_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    assert jax.device_count() == 8
    from repro.api import BACKENDS, ExecutionPolicy, TrajectoryDB

    policy = ExecutionPolicy(batching="periodic", batch_params={"s": 32},
                             num_bins=200)
    db = TrajectoryDB.from_scenario("S2", scale=0.01, policy=policy)
    queries, d = db.scenario_queries, db.scenario_d
    assert db.backend("shard").engine.ways == 8

    results = {name: db.query(queries, d, backend=name) for name in BACKENDS}
    base = results["jnp"]
    assert len(base) > 0
    for name, res in results.items():
        assert len(res) == len(base), (name, len(res), len(base))
        np.testing.assert_array_equal(res.entry_idx, base.entry_idx, err_msg=name)
        np.testing.assert_array_equal(res.query_idx, base.query_idx, err_msg=name)
        np.testing.assert_allclose(res.t_enter, base.t_enter, rtol=1e-4,
                                   atol=1e-3, err_msg=name)
    st = results["shard"].stats
    assert st.pipelined and st.num_syncs <= 2, (st.num_syncs, st.pipelined)
    # cross-pod halo dedup: no (entry, query) pair appears twice
    pairs = list(zip(results["shard"].entry_idx.tolist(),
                     results["shard"].query_idx.tolist()))
    assert len(pairs) == len(set(pairs))
    print("SHARD_BACKEND_OK", len(base), st.num_syncs)

    # PR 4 acceptance: broker tickets over backend="shard" on the 8-pod
    # mesh — incremental slices concatenate byte-identically to db.query's
    # canonical result, <= 2 syncs per dispatch group, per-pod routing.
    broker = db.broker(backend="shard")
    delivered = []
    ticket = broker.submit(queries, d, group_size=2,
                           on_slice=lambda tk, sl: delivered.append(sl))
    assert ticket.state == "pending"
    broker.step()
    assert ticket.state in ("partial", "done")
    res = ticket.result()
    shard_base = results["shard"]
    fields = ("entry_idx", "entry_traj", "entry_seg", "query_idx",
              "t_enter", "t_exit")
    for f in fields:
        np.testing.assert_array_equal(getattr(res, f),
                                      getattr(shard_base, f), err_msg=f)
        concat = np.concatenate([getattr(s.result, f) for s in delivered])
        np.testing.assert_array_equal(concat, getattr(shard_base, f),
                                      err_msg="slice:" + f)
    assert all(s.num_syncs <= 2 for s in delivered), \\
        [s.num_syncs for s in delivered]
    rt = ticket.routing
    assert rt is not None and rt.num_pods == 8
    assert rt.batches == len(ticket.plan.batches)
    dispatched = sum(1 for b in ticket.plan.batches if b.num_candidates > 0)
    assert sum(1 for n in rt.pods_per_batch) == rt.batches
    assert sum(1 for n in rt.pods_per_batch if n > 0) == dispatched
    assert int(rt.pod_hits.sum()) == len(res)
    assert 1 <= max(rt.pods_per_batch) <= 8
    # (query_stream's shard routing is covered in-process in test_api —
    # the forced-8-device CPU mesh is too slow for the re-issue scheduler)
    print("BROKER_SHARD_OK", len(res), len(delivered))
""")


@pytest.mark.slow
def test_five_backend_equivalence_on_8_device_mesh_subprocess():
    """Acceptance: backend="shard" on an 8-device host mesh returns the
    identical canonical result set as the other four backends, with
    <= 2 host syncs per query set and no cross-pod duplicates — and (PR 4)
    broker tickets deliver incremental slices concatenating byte-identically
    to it, <= 2 syncs per dispatch group, with per-pod routing stats."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SHARD_BACKEND_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD_BACKEND_OK" in proc.stdout
    assert "BROKER_SHARD_OK" in proc.stdout


_SPARSE_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    assert jax.device_count() == 8
    from repro.api import ExecutionPolicy, TrajectoryDB

    FIELDS = ("entry_idx", "entry_traj", "entry_seg", "query_idx",
              "t_enter", "t_exit")

    def identical(a, b, label):
        for f in FIELDS:
            assert np.array_equal(getattr(a, f), getattr(b, f)), (label, f)

    # PR 8 acceptance: pruning="hierarchical" x backend="shard" on the
    # 8-pod mesh is byte-identical to the single-device canonical result,
    # sparse dispatch on and off, on C1 / C3 / S2.
    CASES = [
        ("C1", 0.01, dict(num_bins=64, index_kboxes=1)),
        ("C3", 0.01, dict(num_bins=8, index_kboxes=4, max_subranges=64)),
        ("S2", 0.005, dict(num_bins=64, index_kboxes=2)),
    ]
    for scenario, scale, kw in CASES:
        policy = ExecutionPolicy(batching="periodic", batch_params={"s": 8},
                                 pruning="hierarchical", **kw)
        db = TrajectoryDB.from_scenario(scenario, scale=scale, policy=policy)
        queries, d = db.scenario_queries, db.scenario_d
        base = db.query(queries, d, backend="jnp")
        assert len(base) > 0, scenario
        # the pod-local K-box index is really in force (no downgrade)
        eng = db.backend("shard", policy).engine
        assert eng.plan_pruning == "hierarchical", eng.plan_pruning
        assert eng.plan_index is not None
        for sparse in (True, False):
            pol = policy.with_(shard_sparse=sparse)
            res = db.query(queries, d, backend="shard", policy=pol)
            identical(res, base, (scenario, "sparse" if sparse else "dense"))
            st = res.stats
            assert st.num_syncs <= 2, (scenario, sparse, st.num_syncs)
        print("SPARSE_EQUIV_OK", scenario, len(base))

    # Broker tickets: <= 2 syncs per group sparse on/off; the sparse run
    # on the routed C3 workload must actually skip pod executions.
    policy = ExecutionPolicy(batching="periodic", batch_params={"s": 8},
                             pruning="hierarchical", num_bins=8,
                             index_kboxes=4, max_subranges=64)
    db = TrajectoryDB.from_scenario("C3", scale=0.01, policy=policy)
    queries, d = db.scenario_queries, db.scenario_d
    base = db.query(queries, d, backend="jnp")
    for sparse in (True, False):
        pol = policy.with_(shard_sparse=sparse)
        broker = db.broker(backend="shard", policy=pol)
        ticket = broker.submit(queries, d, group_size=2)
        identical(ticket.result(), base, ("broker", sparse))
        assert all(sl.num_syncs <= 2 for sl in ticket.slices()), \\
            [sl.num_syncs for sl in ticket.slices()]
        rt = ticket.routing
        assert rt is not None and rt.num_pods == 8
        assert rt.batches == len(ticket.plan.batches)
        assert int(rt.pod_hits.sum()) == len(base)
        if sparse:
            assert rt.pods_skipped > 0, "routed workload skipped no pods"
            assert rt.padded_interactions_avoided > 0
        else:
            assert rt.pods_skipped == 0
            assert rt.padded_interactions_avoided == 0
    print("SPARSE_BROKER_OK", rt.pods_skipped)

    # Property: skipped pods never drop a true hit — random query subsets
    # routed sparsely return exactly the dense (and single-device) rows.
    rng = np.random.default_rng(0)
    for trial in range(6):
        k = int(rng.integers(3, max(4, len(queries) // 4)))
        idx = np.sort(rng.choice(len(queries), size=k, replace=False))
        sub = queries.take(idx)
        want = db.query(sub, d, backend="jnp")
        dense = db.query(sub, d, backend="shard",
                         policy=policy.with_(shard_sparse=False))
        sparse = db.query(sub, d, backend="shard",
                          policy=policy.with_(shard_sparse=True))
        identical(dense, want, ("prop-dense", trial))
        identical(sparse, want, ("prop-sparse", trial))
    print("SPARSE_PROPERTY_OK")
""")


@pytest.mark.slow
def test_sparse_shard_dispatch_on_8_device_mesh_subprocess():
    """PR 8 acceptance: pod-local hierarchical planning + sparse routed
    dispatch on the 8-pod mesh — byte-identical to the single-device
    canonical on C1/C3/S2 with sparse on and off, <= 2 syncs per broker
    group, ``pods_skipped > 0`` on a routed workload, and a property
    check that skipped pods never drop a true hit."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SPARSE_SHARD_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    for token in ("SPARSE_EQUIV_OK C1", "SPARSE_EQUIV_OK C3",
                  "SPARSE_EQUIV_OK S2", "SPARSE_BROKER_OK",
                  "SPARSE_PROPERTY_OK"):
        assert token in proc.stdout, (token, proc.stdout[-2000:])


_ELASTIC_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ARCHS
    from repro.launch import sharding as shd
    from repro.train import checkpoint as ckpt
    from repro.train import step as step_lib

    cfg = ARCHS["granite-3-2b"].reduced()
    from repro.launch.mesh import make_mesh_compat

    # train state born on an 8-chip (4 data × 2 model) mesh
    mesh_a = make_mesh_compat((4, 2), ("data", "model"))
    state = step_lib.init_train_state(cfg, jax.random.PRNGKey(0))
    specs = step_lib.train_state_specs(cfg)
    sh_a = shd.train_state_shardings(cfg, mesh_a, specs)
    state = jax.tree.map(jax.device_put, state, sh_a)

    with tempfile.TemporaryDirectory() as root:
        ckpt.save(root, 7, state)
        # restore onto a RESHAPED mesh (2 data × 4 model) — elastic reshard
        mesh_b = make_mesh_compat((2, 4), ("data", "model"))
        sh_b = shd.train_state_shardings(cfg, mesh_b, specs)
        restored, step, _ = ckpt.restore(root, state, shardings=sh_b)
        assert step == 7
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        # the restored leaves really live on mesh_b
        leaf = jax.tree.leaves(restored)[0]
        assert leaf.sharding.mesh.shape["model"] == 4
    print("ELASTIC_OK")
""")


@pytest.mark.slow
def test_elastic_reshard_subprocess():
    """Checkpoint written under one mesh restores onto a reshaped mesh with
    identical values — node count can change across restarts."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _ELASTIC_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ELASTIC_OK" in proc.stdout


class TestShardingRules:
    def test_param_specs_all_archs(self):
        """Every full-size parameter gets a divisible spec on the 16×16
        production mesh (this is what made the dry-run compile)."""
        import jax
        from repro.configs import ARCHS
        from repro.launch import sharding as shd
        from repro.models import transformer as T

        class FakeMesh:  # shape-only stand-in; no devices needed
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        for arch, cfg in ARCHS.items():
            specs = T.param_specs(cfg)
            def check(path, leaf):
                for fsdp in (False, True):
                    spec = shd.param_spec(path, leaf.shape, FakeMesh(),
                                          fsdp=fsdp)
                    for dim, ax in zip(leaf.shape, spec):
                        if ax is None:
                            continue
                        ways = 16
                        assert dim % ways == 0, (arch, path, leaf.shape, spec)
            jax.tree_util.tree_map_with_path(check, specs)

    def test_embedding_vocab_parallel(self):
        from repro.configs import ARCHS
        from repro.launch import sharding as shd

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        cfg = ARCHS["granite-3-2b"]
        # padded vocab shards over model on dim 0
        import jax
        from repro.models import transformer as T
        specs = T.param_specs(cfg)

        found = []
        def check(path, leaf):
            names = [str(getattr(p, "key", "")) for p in path]
            if "embed" in names and leaf.ndim == 2:
                spec = shd.param_spec(path, leaf.shape, FakeMesh())
                found.append(spec)
        jax.tree_util.tree_map_with_path(check, specs)
        assert found and all(s[0] == "model" for s in found)
