"""End-to-end query engine vs brute force, across batching algorithms."""
import numpy as np
import pytest

from conftest import random_segments
from repro.core import batching
from repro.core.engine import brute_force
from repro.core.engine import DistanceThresholdEngine
from repro.core.rtree import RTreeEngine


def _check_equal(rs, bf):
    # interval endpoints may differ at f32 fusion-order level (~1e-5 rel)
    # between differently-shaped XLA programs; hits must match exactly.
    rs = rs.sorted_canonical()
    assert len(rs) == len(bf)
    np.testing.assert_array_equal(rs.entry_idx, bf.entry_idx)
    np.testing.assert_array_equal(rs.query_idx, bf.query_idx)
    np.testing.assert_allclose(rs.t_enter, bf.t_enter, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(rs.t_exit, bf.t_exit, rtol=1e-4, atol=1e-3)


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(42)
    db = random_segments(rng, 1500)
    queries = random_segments(rng, 120)
    d = 4.0
    bf = brute_force(db, queries, d)
    assert len(bf) > 0, "fixture produced no hits — adjust parameters"
    return db, queries, d, bf


ALGO_CASES = [
    ("periodic", {"s": 32}),
    ("periodic", {"s": 1}),
    ("periodic", {"s": 120}),
    ("setsplit-fixed", {"num_batches": 6}),
    ("setsplit-minmax", {"min_size": 8, "max_size": 64}),
    ("greedysetsplit-min", {"bound": 16}),
    ("greedysetsplit-max", {"bound": 48}),
]


class TestEngineCorrectness:
    @pytest.mark.parametrize("name,kw", ALGO_CASES)
    def test_engine_equals_brute_force(self, world, name, kw):
        db, queries, d, bf = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        plan = batching.ALGORITHMS[name](eng.index, queries, **kw)
        rs, stats = eng.execute(queries, d, plan)
        _check_equal(rs, bf)
        assert stats.total_hits == len(bf)
        assert stats.num_invocations == plan.num_batches

    def test_overflow_retry_path(self, world):
        db, queries, d, bf = world
        eng = DistanceThresholdEngine(db, num_bins=128, default_capacity=256)
        plan = batching.periodic(eng.index, queries, 64)
        rs, stats = eng.execute(queries, d, plan)
        _check_equal(rs, bf)

    def test_determinism(self, world):
        db, queries, d, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        plan = batching.periodic(eng.index, queries, 32)
        rs1, _ = eng.execute(queries, d, plan)
        rs2, _ = eng.execute(queries, d, plan)
        np.testing.assert_array_equal(rs1.entry_idx, rs2.entry_idx)
        np.testing.assert_array_equal(rs1.query_idx, rs2.query_idx)

    def test_num_bins_invariance(self, world):
        """Result set is independent of the index granularity (bins only
        change the candidate over-approximation)."""
        db, queries, d, bf = world
        for nb in (4, 1000):
            eng = DistanceThresholdEngine(db, num_bins=nb)
            plan = batching.periodic(eng.index, queries, 32)
            rs, _ = eng.execute(queries, d, plan)
            _check_equal(rs, bf)

    def test_zero_distance_threshold(self, world):
        db, queries, _, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        plan = batching.periodic(eng.index, queries, 32)
        rs, stats = eng.execute(queries, 0.0, plan)
        bf0 = brute_force(db, queries, 0.0)
        assert len(rs.sorted_canonical()) == len(bf0)


class TestPipelinedExecutor:
    """The async two-phase executor: O(1) syncs, same results."""

    def test_pipelined_equals_sync_equals_brute(self, world):
        db, queries, d, bf = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        plan = batching.periodic(eng.index, queries, 16)
        rs_pipe, st_pipe = eng.execute(queries, d, plan, pipeline=True)
        rs_sync, st_sync = eng.execute(queries, d, plan, pipeline=False)
        _check_equal(rs_pipe, bf)
        _check_equal(rs_sync, bf)
        assert st_pipe.pipelined and not st_sync.pipelined
        assert st_pipe.total_hits == st_sync.total_hits == len(bf)

    def test_sync_ratio_is_o1_per_query_set(self, world):
        """The acceptance criterion: pipelined execution performs O(1) host
        syncs per query set, vs one (or more) per invocation in sync mode."""
        db, queries, d, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        plan = batching.periodic(eng.index, queries, 8)   # many batches
        _, st_pipe = eng.execute(queries, d, plan, pipeline=True)
        _, st_sync = eng.execute(queries, d, plan, pipeline=False)
        assert st_pipe.num_invocations == plan.num_batches > 2
        assert st_pipe.num_syncs <= 2                     # O(1) per query set
        nonempty = sum(1 for b in plan.batches if b.num_candidates > 0)
        assert st_sync.num_syncs >= nonempty              # O(batches)
        assert st_pipe.num_syncs < st_sync.num_syncs

    def test_pipelined_overflow_retry_converges(self, world):
        """A batch whose hit count exceeds default_capacity: the exact count
        sizes a doubled (power-of-two bucketed) retry that converges in one
        re-dispatch, results still match brute force, retries recorded."""
        db, queries, _, _ = world
        d_all = 60.0                                   # ~everything hits
        bf = brute_force(db, queries, d_all)
        eng = DistanceThresholdEngine(db, num_bins=128, default_capacity=256)
        plan = batching.periodic(eng.index, queries, 64)
        assert any(b.num_ints > 256 for b in plan.batches)
        rs, stats = eng.execute(queries, d_all, plan, pipeline=True)
        _check_equal(rs, bf)
        retried = [b for b in stats.batches if b.retries]
        assert retried, "no batch overflowed — fixture needs adjusting"
        assert all(b.retries == 1 for b in retried)    # one retry suffices
        assert stats.total_retries == len(retried)
        assert stats.num_syncs == 2                    # still O(1)
        # retry capacity doubled at least once: the count that forced the
        # retry exceeded the 256-slot bucket
        assert all(b.num_hits > 256 for b in retried)

    def test_sync_mode_retry_stats_separated(self, world):
        """Satellite: kernel_seconds is first-dispatch device time only;
        retry wall-time lands in retry_seconds."""
        db, queries, _, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128, default_capacity=256)
        plan = batching.periodic(eng.index, queries, 64)
        rs, stats = eng.execute(queries, 60.0, plan, pipeline=False)
        retried = [b for b in stats.batches if b.retries]
        assert retried
        assert all(b.retry_seconds > 0 for b in retried)
        assert all(b.retry_seconds == 0 for b in stats.batches
                   if not b.retries)
        assert stats.retry_seconds == sum(b.retry_seconds for b in retried)
        assert stats.num_syncs == (
            sum(1 for b in stats.batches if b.num_candidates > 0)
            + stats.total_retries)

    @pytest.mark.parametrize("compaction", ["fused", "dense"])
    def test_pallas_compaction_paths_match_brute(self, world, compaction):
        db, queries, d, bf = world
        eng = DistanceThresholdEngine(db, num_bins=128, use_pallas=True,
                                      cand_blk=128, qry_blk=64,
                                      compaction=compaction)
        plan = batching.periodic(eng.index, queries, 64)
        rs, _ = eng.execute(queries, d, plan, pipeline=True)
        _check_equal(rs, bf)

    def test_empty_plan_and_empty_batches(self, world):
        db, queries, d, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        plan = batching.BatchPlan("periodic", {"s": 1}, [], 0.0)
        rs, stats = eng.execute(queries, d, plan, pipeline=True)
        assert len(rs) == 0 and stats.num_invocations == 0


class TestPlannerExecutorSplit:
    """PR 3: planning (capacities, dispatch groups) is a separate layer the
    engine consumes — and grouped plans pipeline with bounded syncs."""

    def test_planner_capacity_formula_matches_engine_default(self, world):
        from repro.core.planner import (QueryPlanner, as_query_plan,
                                        bucket_capacity, size_capacity)
        db, queries, d, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128, default_capacity=512)
        planner = QueryPlanner(eng.index, algorithm="periodic",
                               params={"s": 32}, default_capacity=512)
        qplan = planner.plan(queries)
        assert qplan.algorithm == "periodic" and qplan.params == {"s": 32}
        assert len(qplan.capacities) == qplan.num_batches
        for b, cap in zip(qplan.batches, qplan.capacities):
            assert cap == size_capacity(b, 512)
            assert cap == bucket_capacity(min(512, b.num_candidates * b.size))
        # single group by default — the O(1)-sync shape
        assert qplan.groups == [list(range(qplan.num_batches))]
        # legacy BatchPlan coerces to the same capacities
        legacy = batching.periodic(eng.index, queries, 32)
        coerced = as_query_plan(legacy, default_capacity=512)
        assert coerced.capacities == qplan.capacities

    def test_unknown_algorithm_raises(self, world):
        from repro.core.planner import QueryPlanner
        db, queries, _, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        with pytest.raises(ValueError, match="unknown batching"):
            QueryPlanner(eng.index, algorithm="nope")

    @pytest.mark.parametrize("group_size", [1, 3, None])
    def test_grouped_plan_same_results_bounded_syncs(self, world, group_size):
        from repro.core.planner import QueryPlanner
        db, queries, d, bf = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        planner = QueryPlanner(eng.index, algorithm="periodic",
                               params={"s": 16}, group_size=group_size)
        qplan = planner.plan(queries)
        if group_size is None:
            assert qplan.num_groups == 1
        else:
            import math
            assert qplan.num_groups == math.ceil(qplan.num_batches
                                                 / group_size)
        rs, stats = eng.execute(queries, d, qplan, pipeline=True)
        _check_equal(rs, bf)
        assert stats.pipelined
        assert stats.num_groups == qplan.num_groups
        # <= 2 syncs per dispatch group, exactly 2 only on overflow retries
        assert stats.num_syncs <= 2 * qplan.num_groups

    def test_subplan_is_single_group(self, world):
        from repro.core.planner import QueryPlanner
        db, queries, d, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        planner = QueryPlanner(eng.index, algorithm="periodic",
                               params={"s": 16}, group_size=2)
        qplan = planner.plan(queries)
        sub = qplan.subplan([1, 2])
        assert sub.num_batches == 2 and sub.num_groups == 1
        assert sub.batches[0] is qplan.batches[1]
        assert sub.capacities == qplan.capacities[1:3]
        rs, stats = eng.execute(queries, d, sub)
        assert stats.num_syncs <= 2

    def test_executor_protocol_dispatcher(self, world):
        """The engine's dispatcher satisfies the executor protocol — the
        seam the sharded backend implements too."""
        from repro.core.executor import BatchDispatcher
        db, queries, d, _ = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        disp = eng.dispatcher(queries.packed(), d)
        assert isinstance(disp, BatchDispatcher)

    @pytest.mark.parametrize("pipeline", [True, False])
    def test_group_completion_hook_streams_groups(self, world, pipeline):
        """PR 4: on_group fires once per dispatch group, in group order,
        and the streamed parts concatenate to the full result — on both
        executors."""
        from repro.core.executor import ResultSet
        from repro.core.planner import QueryPlanner
        db, queries, d, bf = world
        eng = DistanceThresholdEngine(db, num_bins=128)
        planner = QueryPlanner(eng.index, algorithm="periodic",
                               params={"s": 16}, group_size=2)
        qplan = planner.plan(queries)
        assert qplan.num_groups >= 2
        seen = []
        rs, stats = eng.execute(
            queries, d, qplan, pipeline=pipeline,
            on_group=lambda gi, g, part: seen.append((gi, g, part)))
        assert [gi for gi, _, _ in seen] == list(range(qplan.num_groups))
        assert [g for _, g, _ in seen] == qplan.groups
        streamed = ResultSet.concatenate([p for _, _, p in seen])
        _check_equal(streamed.sorted_canonical(), bf)
        assert len(streamed) == len(rs)


class TestBucket:
    def test_bucket_edge_cases(self):
        from repro.core.engine import _bucket
        assert _bucket(0, 256) == 256          # n=0 still allocates a block
        assert _bucket(1, 256) == 256
        assert _bucket(255, 256) == 256
        assert _bucket(256, 256) == 256        # exact multiple: no growth
        assert _bucket(257, 256) == 512
        assert _bucket(512, 256) == 512
        assert _bucket(513, 256) == 1024
        assert _bucket(1, 1) == 1
        assert _bucket(7, 1) == 8              # power-of-two ladder from blk


class TestRTreeBaseline:
    def test_rtree_equals_brute_force(self, world):
        db, queries, d, bf = world
        rt = RTreeEngine(db, r=12)
        _check_equal(rt.query(queries, d), bf)

    def test_rtree_parallel_matches(self, world):
        db, queries, d, bf = world
        rt = RTreeEngine(db, r=12)
        _check_equal(rt.query_parallel(queries, d, num_threads=3), bf)

    @pytest.mark.parametrize("r", [1, 4, 32])
    def test_r_invariance(self, world, r):
        """Segments-per-MBB trades performance, never correctness (Fig. 5
        explores the performance side)."""
        db, queries, d, bf = world
        rt = RTreeEngine(db, r=r)
        _check_equal(rt.query(queries, d), bf)


class TestScenarioIntegration:
    def test_scenario_s1_small(self, small_scenario):
        db, queries, d = small_scenario
        bf = brute_force(db, queries, d)
        eng = DistanceThresholdEngine(db, num_bins=500)
        plan = batching.periodic(eng.index, queries, 64)
        rs, _ = eng.execute(queries, d, plan)
        _check_equal(rs, bf)


# ----------------------------------------------------------------------
# The single-device dispatch-shape ladder (ExecutionPolicy.dispatch_ladder)
# ----------------------------------------------------------------------
class TestBucketRows:
    @pytest.mark.parametrize("steps", [1, 2, 3, 4, 8])
    def test_rungs_are_whole_tiles_and_pad_below_one_step(self, steps):
        from repro.core.planner import bucket_rows
        blk = 16
        rungs = set()
        for n in range(0, 40 * blk):
            b = bucket_rows(n, blk, steps)
            tiles = max(-(-n // blk), 1)
            assert b % blk == 0 and b >= max(n, 1)
            assert b // blk - tiles < max(tiles / steps, 1)
            assert bucket_rows(b, blk, steps) == b      # a rung is its own
            rungs.add(b)
        # exact up to 2 * steps tiles, then `steps` rungs per octave
        assert {r // blk for r in rungs if r // blk <= 2 * steps} == set(
            range(1, min(2 * steps, 40) + 1))
        per_octave = steps if steps & (steps - 1) == 0 else steps + 1
        assert all(len({r for r in rungs if lo < r // blk <= 2 * lo})
                   <= per_octave for lo in (16, 32) if lo >= 2 * steps)

    def test_ladder_must_be_a_positive_count(self, world):
        db, _, _, _ = world
        for bad in (0, -2, 1.5, True):
            with pytest.raises(ValueError):
                DistanceThresholdEngine(db, num_bins=16, dispatch_ladder=bad)


@pytest.fixture(scope="module")
def randwalk_db():
    """The paper's RANDWALK-EXP database at scale 0.01 (S9: 7,167
    segments, 891 query segments, d = 50), in batches of 128 queries."""
    from repro.api import ExecutionPolicy, TrajectoryDB
    policy = ExecutionPolicy(batching="periodic", batch_params={"s": 128},
                             num_bins=100)
    db = TrajectoryDB.from_scenario("S9", scale=0.01, policy=policy)
    brute = db.query(db.scenario_queries, db.scenario_d, backend="brute")
    assert len(brute) > 0
    return db, brute


_ROWS = ("entry_idx", "entry_traj", "entry_seg", "query_idx")
_ALL = _ROWS + ("t_enter", "t_exit")
LADDER_MODES = [("jnp", "dense"), ("pallas", "fused"), ("pallas", "dense"),
                ("pallas", "fused_rowloop")]


@pytest.mark.parametrize("pruning", ["spatial", "hierarchical", "none"])
@pytest.mark.parametrize("backend,compaction", LADDER_MODES)
def test_randwalk_ladder_matches_brute_force(randwalk_db, backend,
                                             compaction, pruning):
    """Padded dispatches give the exact dispatches' canonical result, byte
    for byte, and both give brute force's rows (intervals to the usual
    cross-backend f32 tolerance)."""
    db, brute = randwalk_db
    queries, d = db.scenario_queries, db.scenario_d
    exact, padded = (db.query(queries, d, backend=backend,
                              policy=db.policy.with_(
                                  compaction=compaction, pruning=pruning,
                                  dispatch_ladder=ladder))
                     for ladder in (None, 2))
    for f in _ALL:
        np.testing.assert_array_equal(getattr(padded, f), getattr(exact, f),
                                      err_msg=f)
    for f in _ROWS:
        np.testing.assert_array_equal(getattr(exact, f), getattr(brute, f),
                                      err_msg=f)
    for f in ("t_enter", "t_exit"):
        np.testing.assert_allclose(getattr(exact, f), getattr(brute, f),
                                   rtol=1e-4, atol=1e-3, err_msg=f)
    assert exact.stats.counts["pad_rows"] == 0
    assert 0 < padded.stats.counts["pad_rows"] < padded.stats.counts[
        "dispatched_rows"]


def _record_blocks(monkeypatch):
    """Every ``ops.query_block`` call of the engine: (candidate rows,
    query rows, valid_rows)."""
    from repro.core import engine
    real, seen = engine.ops.query_block, []

    def query_block(entries, queries, d, **kw):
        seen.append((entries.shape[0], queries.shape[0],
                     kw.get("valid_rows")))
        return real(entries, queries, d, **kw)

    monkeypatch.setattr(engine.ops, "query_block", query_block)
    return seen


@pytest.mark.parametrize("ladder", [None, 2])
def test_dispatch_hands_raw_or_laddered_slices(randwalk_db, monkeypatch,
                                               ladder):
    """Unset, the dispatcher hands ``query_block`` the raw slices, as
    before the ladder existed; set, slices padded onto the ladder, with
    the real row counts beside them."""
    from repro.core.planner import bucket_rows
    db, _ = randwalk_db
    queries, d = db.scenario_queries, db.scenario_d
    seen = _record_blocks(monkeypatch)
    res = db.query(queries, d, backend="jnp",
                   policy=db.policy.with_(dispatch_ladder=ladder))
    raw = [(b.num_candidates, b.size) for b in res.plan.batches
           if b.num_candidates > 0]
    assert len(seen) == len(raw) == res.stats.num_invocations
    if ladder is None:
        assert [(c, q) for c, q, _ in seen] == raw
        assert all(v is None for _, _, v in seen)
    else:
        assert [v for _, _, v in seen] == raw
        assert [(c, q) for c, q, _ in seen] == [
            (bucket_rows(c, 256, ladder), bucket_rows(q, 256, ladder))
            for c, q in raw]


def test_many_batch_lengths_lower_few_shapes(randwalk_db, monkeypatch):
    """A query set whose batches have tens of distinct candidate lengths
    lowers no more ``_query_block_jit`` shapes than the ladder has rungs
    for, times the capacities met."""
    from repro.api import ExecutionPolicy
    from repro.core.planner import bucket_rows
    from repro.kernels import ops
    db, _ = randwalk_db
    queries, d = db.scenario_queries, db.scenario_d
    real, keys = ops._query_block_jit, []

    def jitted(entries, queries, d, **kw):
        keys.append((entries.shape, queries.shape, kw["capacity"],
                     kw["pruning"]))
        return real(entries, queries, d, **kw)

    monkeypatch.setattr(ops, "_query_block_jit", jitted)
    many = ExecutionPolicy(batching="periodic", batch_params={"s": 16},
                           num_bins=100)
    shapes = {}
    for ladder in (None, 4):
        keys.clear()
        res = db.query(queries, d, backend="jnp",
                       policy=many.with_(dispatch_ladder=ladder))
        shapes[ladder] = set(keys)
    lengths = {b.num_candidates for b in res.plan.batches}
    assert len({k[0] for k in shapes[None]}) == len(lengths) > 20
    rungs = {bucket_rows(n, 256, 4) for n in lengths}
    qrungs = {bucket_rows(b.size, 256, 4) for b in res.plan.batches}
    caps = {k[2] for k in shapes[4]}
    assert {k[0][0] for k in shapes[4]} == rungs
    assert len(shapes[4]) <= len(rungs) * len(qrungs) * len(caps)
    assert len(shapes[4]) * 4 < len(shapes[None])


def test_box_test_sees_only_real_rows(monkeypatch):
    """With host pad rows, the spatial box test runs on the real rows
    alone: the same gating as unpadded, the real tiles' boxes unchanged,
    and every tile of pad rows the empty box."""
    from repro.kernels import ops
    got = {}

    def jitted(entries, queries, d, **kw):
        got[entries.shape[0]] = kw
        return {}

    monkeypatch.setattr(ops, "_query_block_jit", jitted)
    rng = np.random.default_rng(3)
    blk = 8
    # Two far-apart clusters of entries, queries near the first: tiles of
    # the second cluster are skippable.
    e = rng.uniform(0, 5, (3 * blk + 3, 8)).astype(np.float32)
    e[2 * blk:, 0:6] += 500.0
    e[:, 6] = 0.0
    e[:, 7] = 1.0
    q = e[:5].copy()
    t_e, t_q = ops.pad_instants(1.0, q)
    e_pad = ops.pad_block(e, 8 * blk, t_e)
    q_pad = ops.pad_block(q, 2 * blk, t_q)
    kw = dict(capacity=64, use_pallas=True, cand_blk=blk, qry_blk=blk,
              compaction="fused", pruning="spatial")
    ops.query_block(e, q, np.float32(2.0), **kw)
    ops.query_block(e_pad, q_pad, np.float32(2.0), valid_rows=(len(e), 5),
                    **kw)
    exact, padded = got[len(e)], got[8 * blk]
    assert exact["pruning"] == padded["pruning"] == "spatial"
    nt = exact["e_mbr"].shape[0]
    np.testing.assert_array_equal(padded["e_mbr"][:nt], exact["e_mbr"])
    assert np.all(padded["e_mbr"][nt:, 0:3] == np.inf)
    assert np.all(padded["e_mbr"][nt:, 3:6] == -np.inf)
    np.testing.assert_array_equal(padded["q_mbr"][:1], exact["q_mbr"])
    assert padded["d_prune"] == exact["d_prune"]
    # Pad rows at the origin would have grown the last real tile's box.
    assert exact["e_mbr"][nt - 1, 0] > 400
    # Nothing skippable among the real tiles: the unarmed kernel, padded
    # or not.
    got.clear()
    near = e[:2 * blk]
    ops.query_block(near, q, np.float32(2.0), **kw)
    ops.query_block(ops.pad_block(near, 4 * blk, t_e), q_pad,
                    np.float32(2.0), valid_rows=(2 * blk, 5), **kw)
    assert got[2 * blk]["pruning"] == got[4 * blk]["pruning"] == "none"


def test_pad_rows_never_meet_real_rows_or_each_other():
    from repro.kernels import ops
    q = np.zeros((3, 8), np.float32)
    q[:, 7] = [4.0, 9.5, 2.0]
    t_e, t_q = ops.pad_instants(7.0, q)
    assert t_e > 9.5 and t_q > t_e
    block = ops.pad_block(q, 5, t_q)
    np.testing.assert_array_equal(block[:3], q)
    assert np.all(block[3:, 6:8] == t_q) and np.all(block[3:, :6] == 0)
    assert ops.pad_block(q, 3, t_q) is q
