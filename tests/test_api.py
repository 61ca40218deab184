"""The ``repro.api`` facade: backend equivalence, caller-order results,
policy plumbing, streaming, serving, and the deprecation shims."""
import numpy as np
import pytest

from _hypothesis_compat import given, settings
from _hypothesis_compat import strategies as st
from conftest import random_segments
from repro.api import (BACKENDS, BruteBackend, EngineBackend, ExecutionPolicy,
                       QueryBackend, QueryResult, RTreeBackend, TrajectoryDB)
from repro.core.segments import SegmentArray


@pytest.fixture(scope="module")
def scenario_db():
    """A scaled-down paper scenario through the facade (S2: GALAXY, d=5)."""
    policy = ExecutionPolicy(batching="periodic", batch_params={"s": 32},
                             num_bins=200)
    db = TrajectoryDB.from_scenario("S2", scale=0.01, policy=policy)
    assert db.scenario_queries is not None and db.scenario_d is not None
    return db


def _rows(result: QueryResult):
    return (result.entry_idx, result.entry_traj, result.entry_seg,
            result.query_idx)


# ----------------------------------------------------------------------
# Backend equivalence: the acceptance criterion.
# ----------------------------------------------------------------------
def test_backend_equivalence_on_scenario(scenario_db):
    """All five backends — and, for each, the compaction strategies and
    both executors — produce identical canonical result sets, with
    query_idx in caller order, on a trajgen scenario.  (compaction= only
    changes the device path for "pallas"; pipeline= only the engine
    backends; both are accepted no-ops elsewhere.)"""
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    assert set(BACKENDS) == {"pallas", "jnp", "rtree", "brute", "shard"}
    results = {}
    for name in BACKENDS:
        for compaction in ("fused", "dense"):
            for pipeline in (True, False):
                if name in ("rtree", "brute") and (compaction != "fused"
                                                   or not pipeline):
                    continue     # knobs don't reach the CPU baselines
                res = db.query(queries, d, backend=name,
                               compaction=compaction, pipeline=pipeline)
                results[(name, compaction, pipeline)] = res
    base = results[("jnp", "fused", True)]
    assert len(base) > 0, "scenario produced no hits — adjust scale/d"
    for (name, compaction, pipeline), res in results.items():
        label = (name, compaction, pipeline)
        assert res.backend == name
        assert len(res) == len(base), (label, len(res), len(base))
        for a, b in zip(_rows(res), _rows(base)):
            np.testing.assert_array_equal(a, b, err_msg=str(label))
        # interval endpoints may differ at f32 fusion-order level between
        # differently-shaped XLA programs; hits must match exactly.
        np.testing.assert_allclose(res.t_enter, base.t_enter,
                                   rtol=1e-4, atol=1e-3, err_msg=str(label))
        np.testing.assert_allclose(res.t_exit, base.t_exit,
                                   rtol=1e-4, atol=1e-3, err_msg=str(label))
    # the engine backends report the O(1)-sync property through the facade
    st = results[("pallas", "fused", True)].stats
    assert st.pipelined and st.num_syncs <= 2
    assert results[("jnp", "fused", False)].stats.num_syncs >= 1
    # acceptance: the sharded path keeps <= 2 host syncs per query set
    st_shard = results[("shard", "fused", True)].stats
    assert st_shard is not None
    assert st_shard.pipelined and st_shard.num_syncs <= 2


def test_backend_protocol_and_cache(scenario_db):
    from repro.api import ShardBackend
    db = scenario_db
    assert isinstance(db.backend("jnp"), EngineBackend)
    assert isinstance(db.backend("rtree"), RTreeBackend)
    assert isinstance(db.backend("brute"), BruteBackend)
    assert isinstance(db.backend("shard"), ShardBackend)
    for name in BACKENDS:
        assert isinstance(db.backend(name), QueryBackend)
        assert db.backend(name) is db.backend(name)      # cached
    # pallas/jnp engines share the database, index and packed copy
    assert db.engine("pallas").index is db.engine("jnp").index
    assert db.engine("pallas").use_pallas and not db.engine("jnp").use_pallas
    with pytest.raises(ValueError):
        db.backend("cuda")
    with pytest.raises(ValueError):
        db.engine("brute")
    with pytest.raises(ValueError):
        db.engine("shard")              # mesh engine is not a device engine


# ----------------------------------------------------------------------
# Caller-order results.
# ----------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), d=st.floats(1.0, 6.0),
       backend=st.sampled_from(["jnp", "brute"]))
def test_unsorted_queries_return_caller_order(seed, d, backend):
    """db.query on UNSORTED queries returns indices in the caller's
    original order: shuffling the query array only permutes query_idx."""
    rng = np.random.default_rng(seed)
    db = TrajectoryDB.from_segments(random_segments(rng, 400),
                                    policy=ExecutionPolicy(num_bins=64))
    queries = random_segments(rng, 60)              # sorted by construction
    perm = rng.permutation(len(queries))
    shuffled = queries.take(perm)
    assert not shuffled.is_sorted() or np.all(np.diff(queries.ts) == 0)

    base = db.query(queries, float(d), backend=backend)
    got = db.query(shuffled, float(d), backend=backend)
    assert len(got) == len(base)
    # Row (e, q) in the sorted run must appear as (e, perm^-1[q]) in the
    # shuffled run — i.e. indices refer to the array the caller passed.
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    expect_q = inv[base.query_idx]
    rank = np.lexsort((base.entry_idx, expect_q))
    np.testing.assert_array_equal(got.query_idx, expect_q[rank])
    np.testing.assert_array_equal(got.entry_idx, base.entry_idx[rank])
    # And every reported pair refers to the caller's own segment: the
    # query segment's temporal extent must contain the interval.
    qts = shuffled.ts[got.query_idx]
    qte = shuffled.te[got.query_idx]
    assert np.all(got.t_enter >= qts - 1e-3)
    assert np.all(got.t_exit <= qte + 1e-3)


def test_unsorted_queries_regression_engine_guard(scenario_db):
    """The engine's sortedness ValueError stays for direct users but is
    unreachable through the facade (which auto-sorts)."""
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    rng = np.random.default_rng(7)
    perm = rng.permutation(len(queries))
    shuffled = queries.take(perm)
    assert not shuffled.is_sorted()
    # Direct engine call: still guarded.
    plan = db.plan(queries)
    with pytest.raises(ValueError, match="sorted"):
        db.engine("jnp").execute(shuffled, d, plan)
    # Facade call: auto-sorts; same hits once indices are mapped back to
    # the common (sorted-caller) frame.  shuffled[i] == queries[perm[i]].
    a = db.query(shuffled, d)
    b = db.query(queries, d)
    assert len(a) == len(b)
    a_q = perm[a.query_idx]
    a_rank = np.lexsort((a.entry_idx, a_q))
    b_rank = np.lexsort((b.entry_idx, b.query_idx))
    np.testing.assert_array_equal(a_q[a_rank], b.query_idx[b_rank])
    np.testing.assert_array_equal(a.entry_idx[a_rank], b.entry_idx[b_rank])


# ----------------------------------------------------------------------
# Policy + result plumbing.
# ----------------------------------------------------------------------
def test_policy_overrides_and_defaults(scenario_db):
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    res = db.query(queries, d, batching="periodic", s=16)
    assert res.plan.algorithm == "periodic" and res.plan.params == {"s": 16}
    res2 = db.query(queries, d, batching="greedysetsplit-min")
    assert res2.plan.algorithm == "greedysetsplit-min"
    assert len(res2) == len(res)
    # defaults resolve for every algorithm without explicit params
    for algo in ("periodic", "setsplit-fixed", "setsplit-max",
                 "setsplit-minmax", "greedysetsplit-min",
                 "greedysetsplit-max"):
        params = ExecutionPolicy(batching=algo).resolved_batch_params(200)
        assert params
    with pytest.raises(ValueError):
        ExecutionPolicy(batching="nope").resolved_batch_params(10)
    # with_ is a functional update: new value object, original untouched
    pol = db.policy.with_(capacity=128)
    assert pol.capacity == 128
    assert db.policy.capacity == 4096
    assert pol is not db.policy


def test_per_call_policy_builds_matching_backend(scenario_db):
    """A per-call policy's backend knobs are honored, not silently dropped:
    different knobs get their own cached adapter."""
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    pol = db.policy.with_(rtree_threads=2, rtree_r=4, capacity=512)
    assert db.backend("rtree", pol) is not db.backend("rtree")
    assert db.backend("rtree", pol).threads == 2
    assert db.backend("rtree", pol).engine.tree.r == 4
    assert db.backend("rtree", pol) is db.backend("rtree", pol)    # cached
    assert db.engine("jnp", pol).default_capacity == 512
    assert db.engine("jnp").default_capacity == db.policy.capacity
    res = db.query(queries, d, backend="rtree", policy=pol)
    base = db.query(queries, d, backend="rtree")
    assert len(res) == len(base)
    np.testing.assert_array_equal(res.entry_idx, base.entry_idx)


def test_mismatched_batch_params_raise_value_error(scenario_db):
    """Forgetting batching=... with algorithm-specific params fails with a
    facade-level ValueError naming the mismatch, not a deep TypeError."""
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    with pytest.raises(ValueError, match="greedysetsplit-min"):
        db.query(queries, d, batching="greedysetsplit-min", s=48)


def test_query_stream_empty_queries(scenario_db):
    db = scenario_db
    res, sched = db.query_stream(SegmentArray.empty(), db.scenario_d)
    assert len(res) == 0 and sched.completed == 0


def test_query_result_helpers(scenario_db):
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    res = db.query(queries, d)
    # canonical ordering: non-decreasing query_idx, entry_idx within
    assert np.all(np.diff(res.query_idx) >= 0)
    trajs = res.matched_trajectories()
    assert trajs.size == np.unique(res.entry_traj).size
    one = res.matches_for(int(res.query_idx[0]))
    assert len(one) >= 1
    assert np.all(one.query_idx == res.query_idx[0])
    rs = res.to_result_set()
    assert len(rs) == len(res)
    # empty query set short-circuits
    empty = db.query(SegmentArray.empty(), d)
    assert len(empty) == 0


# ----------------------------------------------------------------------
# Streaming + serving.
# ----------------------------------------------------------------------
def test_query_stream_matches_query(scenario_db):
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    base = db.query(queries, d)
    res, sched = db.query_stream(queries, d)
    assert sched.completed == res.plan.num_batches
    assert len(res) == len(base)
    for a, b in zip(_rows(res), _rows(base)):
        np.testing.assert_array_equal(a, b)
    # acceptance: workers are handed batch *groups* (>= 2 batches per call
    # whenever the plan has >= 2 batches), each one pipelined dispatch
    assert res.plan.num_batches >= 2
    assert sched.groups < res.plan.num_batches
    assert max(sched.group_sizes) >= 2
    assert sched.batches_per_call >= 2
    # explicit group size flows through the policy
    res2, sched2 = db.query_stream(
        queries, d, policy=db.policy.with_(stream_group_size=1))
    assert sched2.groups == res2.plan.num_batches
    assert len(res2) == len(base)
    with pytest.raises(ValueError):
        db.query_stream(queries, d, backend="rtree")


def test_query_stream_shard_routes_per_pod(scenario_db):
    """PR 4: query_stream reaches the ShardedEngine pods — groups route
    through the PodRouter and SchedulerStats carries the routing view."""
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    base = db.query(queries, d)
    res, sched = db.query_stream(queries, d, backend="shard")
    assert len(res) == len(base)
    for a, b in zip(_rows(res), _rows(base)):
        np.testing.assert_array_equal(a, b)
    assert sched.completed == res.plan.num_batches
    assert sched.routing is not None
    assert sched.routing.batches >= res.plan.num_batches   # incl. re-issue
    assert sched.routing.num_pods >= 1
    assert int(sched.routing.pod_hits.sum()) >= len(base)


def test_trajectory_query_service(scenario_db):
    from repro.serve import TrajectoryQueryService
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    with pytest.warns(DeprecationWarning, match="QueryBroker"):
        svc = TrajectoryQueryService(db, backend="jnp")
    base = db.query(queries, d)
    rng = np.random.default_rng(3)
    shuffled = queries.take(rng.permutation(len(queries)))
    u1 = svc.submit(queries, d)
    u2 = svc.submit(shuffled, d)
    assert svc.pending == 2
    responses = svc.drain()
    assert svc.pending == 0 and svc.completed == 2
    assert set(responses) == {u1, u2}
    assert responses[u1].ok and responses[u2].ok
    assert len(responses[u1].result) == len(base)
    assert len(responses[u2].result) == len(base)
    assert responses[u1].latency_seconds > 0
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):
            TrajectoryQueryService(db, backend="brute")


def test_trajectory_query_service_drain_surfaces_errors(scenario_db,
                                                        monkeypatch):
    """Satellite regression: a request that raises must come back as an
    errored QueryResponse (previously it was popped and silently lost) and
    the rest of the queue must still drain."""
    from repro.serve import TrajectoryQueryService
    db = scenario_db
    queries, d = db.scenario_queries, db.scenario_d
    with pytest.warns(DeprecationWarning):
        svc = TrajectoryQueryService(db, backend="jnp")
    u_bad = svc.submit(queries, d)
    u_ok = svc.submit(queries, d)
    orig = db.query_stream
    calls = {"n": 0}

    def flaky(q, dd, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected executor failure")
        return orig(q, dd, **kw)

    monkeypatch.setattr(db, "query_stream", flaky)
    responses = svc.drain()
    assert set(responses) == {u_bad, u_ok}
    assert not responses[u_bad].ok
    assert responses[u_bad].result is None
    assert isinstance(responses[u_bad].error, RuntimeError)
    assert responses[u_ok].ok and len(responses[u_ok].result) > 0
    assert svc.failed == 1 and svc.completed == 1 and svc.pending == 0


# ----------------------------------------------------------------------
# Deprecation shims.
# ----------------------------------------------------------------------
def test_core_engine_names_deprecated_but_working():
    with pytest.warns(DeprecationWarning, match="repro.api"):
        from repro.core import DistanceThresholdEngine  # noqa: F401
    with pytest.warns(DeprecationWarning):
        from repro.core import brute_force  # noqa: F401
    # the defining module stays warning-free
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        from repro.core.engine import DistanceThresholdEngine  # noqa: F401,F811


def test_top_level_reexports():
    import repro
    assert repro.TrajectoryDB is TrajectoryDB
    assert repro.ExecutionPolicy is ExecutionPolicy
    with pytest.raises(AttributeError):
        repro.does_not_exist
