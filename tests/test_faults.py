"""PR 10 chaos suite: deterministic fault injection, broker retry/backoff,
the graceful-degradation ladder, structured capacity/pod errors, and
facade input hardening.

The acceptance bar: under every injected fault kind, a query either
returns the same result rows as the clean run (indices byte-identical;
interval endpoints byte-identical within a backend, float-close across
backend/compaction rungs — the kernels order the arithmetic differently)
or raises a *structured* error — never a silently wrong or silently
partial result.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st
from conftest import random_segments
from repro import faults
from repro.api import ExecutionPolicy, TrajectoryDB
from repro.core.errors import CapacityError, PodFailedError
from repro.core.segments import SegmentArray
from repro.serve.cache import SliceCache
from repro.serve.retry import RetryPolicy

_IDX_FIELDS = ("entry_idx", "entry_traj", "entry_seg", "query_idx")
_T_FIELDS = ("t_enter", "t_exit")


def _assert_identical(res, base, label=""):
    """Byte-identity — same-backend comparisons."""
    for f in _IDX_FIELDS + _T_FIELDS:
        np.testing.assert_array_equal(getattr(res, f), getattr(base, f),
                                      err_msg=f"{label}:{f}")


def _assert_same_rows(res, base, label=""):
    """Exact indices, float-close interval times — for results that may
    have crossed a backend/compaction rung (last-ulp differences)."""
    for f in _IDX_FIELDS:
        np.testing.assert_array_equal(getattr(res, f), getattr(base, f),
                                      err_msg=f"{label}:{f}")
    for f in _T_FIELDS:
        np.testing.assert_allclose(getattr(res, f), getattr(base, f),
                                   rtol=1e-4, atol=1e-3,
                                   err_msg=f"{label}:{f}")


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(7)
    db = TrajectoryDB.from_segments(
        random_segments(rng, 600),
        policy=ExecutionPolicy(num_bins=64, batching="periodic",
                               batch_params={"s": 16}))
    queries = random_segments(rng, 80)
    return db, queries, 4.0


@pytest.fixture(scope="module")
def base(world):
    db, queries, d = world
    return db.query(queries, d, backend="jnp")


#: fast backoff so retry tests don't sleep for real
_FAST = dict(base_backoff=0.002, max_backoff=0.01)


# ----------------------------------------------------------------------
# FaultPlan semantics.
# ----------------------------------------------------------------------
class TestFaultPlan:
    def test_kind_validation(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.FaultSpec("engine.dispatch", "explode")

    def test_after_times_counting(self):
        plan = faults.FaultPlan(
            [faults.FaultSpec("s", "error", times=2, after=1)])
        fired = 0
        for _ in range(5):
            try:
                plan.inject("s", {})
            except faults.InjectedKernelError:
                fired += 1
        assert fired == 2
        assert plan.calls["s"] == 5
        assert [e.index for e in plan.events] == [2, 3]

    def test_match_filters_ctx(self):
        plan = faults.FaultPlan(
            [faults.FaultSpec("s", "error", times=None, match={"pod": 2})])
        plan.inject("s", {"pod": 1})             # no fire
        with pytest.raises(faults.InjectedKernelError):
            plan.inject("s", {"pod": 2})

    def test_probability_deterministic(self):
        def run(seed):
            plan = faults.FaultPlan(
                [faults.FaultSpec("s", "error", times=None,
                                  probability=0.5)], seed=seed)
            hits = []
            for i in range(32):
                try:
                    plan.inject("s", {"i": i})
                    hits.append(0)
                except faults.InjectedKernelError:
                    hits.append(1)
            return hits
        a, b = run(3), run(3)
        assert a == b                       # replayable
        assert 0 < sum(a) < 32              # actually probabilistic
        assert run(4) != a                  # seed-sensitive

    def test_arm_disarm_and_module_hooks(self):
        assert not faults.armed()
        assert faults.corrupt("s", 7) == 7   # disarmed passthrough
        plan = faults.FaultPlan(
            [faults.FaultSpec("s", "corrupt_count", factor=2.0, bias=1)])
        with faults.active(plan):
            assert faults.armed()
            with pytest.raises(RuntimeError, match="already armed"):
                faults.arm(plan)
            assert faults.corrupt("s", 7) == 15
        assert not faults.armed()
        rep = plan.report()
        assert rep["fired"] == [1] and rep["calls"]["s"] == 1

    def test_resource_exhausted_message(self):
        plan = faults.FaultPlan([faults.FaultSpec("s", "resource_exhausted")])
        with pytest.raises(faults.InjectedResourceExhausted,
                           match="RESOURCE_EXHAUSTED"):
            plan.inject("s", {})

    def test_pod_dropout_raises_structured(self):
        plan = faults.FaultPlan([faults.FaultSpec("shard.pod",
                                                  "pod_dropout")])
        with pytest.raises(PodFailedError) as ei:
            plan.inject("shard.pod", {"pod": 3})
        assert ei.value.pod == 3


class TestRetryPolicy:
    def test_backoff_deterministic_and_bounded(self):
        pol = RetryPolicy(base_backoff=0.1, backoff_factor=2.0,
                          max_backoff=0.5, jitter=0.25, seed=1)
        vals = [pol.backoff_seconds(5, 0, a) for a in (1, 2, 3, 4, 5)]
        assert vals == [pol.backoff_seconds(5, 0, a) for a in (1, 2, 3, 4, 5)]
        for a, v in enumerate(vals, start=1):
            base = min(0.1 * 2.0 ** (a - 1), 0.5)
            assert base * 0.75 <= v <= base * 1.25

    def test_straggler_timeout(self):
        assert RetryPolicy().straggler_timeout(1.0) is None
        pol = RetryPolicy(straggler_slack=3.0, straggler_min_timeout=0.05)
        assert pol.straggler_timeout(1.0) == 3.0
        assert pol.straggler_timeout(0.0) == 0.05
        assert pol.straggler_timeout(None) == 0.05

    def test_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(jitter=2.0)


# ----------------------------------------------------------------------
# Disarmed hooks are no-ops: every backend byte-identical to itself.
# ----------------------------------------------------------------------
def test_disarmed_hooks_are_noops(world):
    db, queries, d = world
    assert not faults.armed()
    for backend in ("jnp", "pallas", "shard"):
        a = db.query(queries, d, backend=backend)
        b = db.query(queries, d, backend=backend)
        _assert_identical(a, b, backend)


# ----------------------------------------------------------------------
# Injected faults on the plain query path surface as errors (no broker,
# no retry policy — fail fast, never silently wrong).
# ----------------------------------------------------------------------
def test_query_path_surfaces_injected_errors(world, base):
    db, queries, d = world
    spec = faults.FaultSpec("ops.query_block", "error", times=1)
    with faults.active(faults.FaultPlan([spec])) as plan:
        with pytest.raises(faults.InjectedKernelError):
            db.query(queries, d, backend="jnp")
    assert plan.events and plan.events[0].site == "ops.query_block"
    # the plan disarmed: the very next query is clean and identical
    _assert_identical(db.query(queries, d, backend="jnp"), base)


def test_corrupted_counts_cannot_corrupt_results(world, base):
    """Mask-based marshalling: an over- or under-reported overflow count
    never drops or duplicates rows — the result stays byte-identical."""
    db, queries, d = world
    for factor, bias in ((8.0, 3), (0.0, 0), (1.0, -5)):
        spec = faults.FaultSpec("engine.count", "corrupt_count",
                                times=None, factor=factor, bias=bias)
        with faults.active(faults.FaultPlan([spec])):
            res = db.query(queries, d, backend="jnp")
        _assert_identical(res, base, f"corrupt factor={factor} bias={bias}")


# ----------------------------------------------------------------------
# Satellite 1: bounded overflow-retry loop with structured CapacityError.
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def adversarial():
    """All-pairs-hit workload: every entry within d of every query during
    a shared time window — the overflow-retry worst case."""
    rng = np.random.default_rng(11)
    n, q = 96, 12

    def cluster(m):
        ts = np.sort(rng.uniform(0.0, 1.0, m)).astype(np.float32)
        te = (ts + 9.0).astype(np.float32)
        p0 = rng.uniform(0, 0.5, (m, 3)).astype(np.float32)
        p1 = (p0 + rng.normal(0, 0.1, (m, 3))).astype(np.float32)
        return SegmentArray(
            xs=p0[:, 0], ys=p0[:, 1], zs=p0[:, 2],
            xe=p1[:, 0], ye=p1[:, 1], ze=p1[:, 2], ts=ts, te=te,
            seg_id=np.arange(m, dtype=np.int32),
            traj_id=np.arange(m, dtype=np.int32) % 5)
    return cluster(n), cluster(q), 50.0


def test_capacity_error_is_structured_and_exact(adversarial):
    entries, queries, d = adversarial
    pol = ExecutionPolicy(capacity=16, max_capacity_retries=0,
                          batching="periodic", batch_params={"s": 4},
                          num_bins=8)
    db = TrajectoryDB.from_segments(entries, policy=pol)
    with pytest.raises(CapacityError) as ei:
        db.query(queries, d, backend="jnp")
    err = ei.value
    assert err.count > err.capacity
    assert err.retries == 0 and err.batch_index is not None
    assert str(err.capacity) in str(err) and "max_capacity_retries" in str(err)


def test_capacity_retry_converges_within_bound(adversarial):
    entries, queries, d = adversarial
    pol = ExecutionPolicy(capacity=16, batching="periodic",
                          batch_params={"s": 4}, num_bins=8)
    db = TrajectoryDB.from_segments(entries, policy=pol)
    res = db.query(queries, d, backend="jnp")       # default bound: fine
    from repro.core.engine import brute_force
    bf = brute_force(db.segments, queries, d)
    assert len(res.entry_idx) == len(bf.entry_idx) > 0
    assert res.stats.total_retries >= 1             # the workload overflowed
    # sync-loop executor honors the bound too
    with pytest.raises(CapacityError):
        db.query(queries, d, backend="jnp", pipeline=False,
                 policy=pol.with_(max_capacity_retries=0, pipeline=False))


# ----------------------------------------------------------------------
# Satellite 2: facade input hardening.
# ----------------------------------------------------------------------
class TestValidation:
    def _segs(self, **overrides):
        rng = np.random.default_rng(0)
        segs = random_segments(rng, 32)
        for name, (idx, val) in overrides.items():
            getattr(segs, name)[idx] = val
        return segs

    def test_nan_coordinate_rejected_at_construction(self):
        with pytest.raises(ValueError, match="non-finite"):
            TrajectoryDB.from_segments(self._segs(xs=(3, np.nan)))

    def test_inf_coordinate_rejected_in_query(self, world):
        db, _, d = world
        with pytest.raises(ValueError, match="queries.*non-finite"):
            db.query(self._segs(ze=(0, np.inf)), d)

    def test_nonfinite_timestamp_rejected(self):
        with pytest.raises(ValueError, match="timestamps"):
            TrajectoryDB.from_segments(self._segs(ts=(1, np.nan)))

    def test_zero_length_interval_rejected(self, world):
        db, _, d = world
        segs = self._segs()
        segs.te[4] = segs.ts[4]
        with pytest.raises(ValueError, match="zero-length or inverted"):
            db.query(segs, d)

    @pytest.mark.parametrize("bad_d", [np.nan, np.inf, -np.inf, -1.0])
    def test_bad_threshold_rejected(self, world, bad_d):
        db, queries, _ = world
        with pytest.raises(ValueError, match="finite and >= 0"):
            db.query(queries, bad_d)

    def test_query_stream_validates(self, world):
        db, queries, _ = world
        with pytest.raises(ValueError, match="finite and >= 0"):
            db.query_stream(queries, float("nan"))

    def test_broker_submit_validates(self, world):
        db, _, d = world
        broker = db.broker(backend="jnp")
        with pytest.raises(ValueError, match="non-finite"):
            broker.submit(self._segs(xs=(0, np.nan)), d)

    @settings(max_examples=10)
    @given(n=st.integers(min_value=1, max_value=64),
           seed=st.integers(min_value=0, max_value=2**31 - 1),
           d=st.floats(min_value=0.0, max_value=1e6),
           max_len=st.floats(min_value=0.01, max_value=100.0))
    def test_validation_never_rejects_finite_workloads(self, n, seed, d,
                                                       max_len):
        """Property: the validators accept every finite workload with
        strictly positive interval lengths."""
        from repro.api import _validate_segments, _validate_threshold
        rng = np.random.default_rng(seed)
        segs = random_segments(rng, n, max_len=max_len)
        _validate_segments(segs, "entry segments")   # must not raise
        assert _validate_threshold(d) == float(d)


# ----------------------------------------------------------------------
# Broker-level retry, backoff, and the degradation ladder.
# ----------------------------------------------------------------------
class TestBrokerRetry:
    def test_transient_kernel_error_retried(self, world, base):
        db, queries, d = world
        broker = db.broker(backend="jnp", retry=RetryPolicy(**_FAST))
        spec = faults.FaultSpec("engine.dispatch", "error", times=1)
        with faults.active(faults.FaultPlan([spec])):
            t = broker.submit(queries, d)
            res = t.result()
        _assert_identical(res, base)
        assert t.health.retries == 1
        assert t.health.attempts[0] == 2
        assert not res.degraded and not t.health.degraded
        assert broker.inflight_interactions == 0

    def test_resource_exhausted_backs_off_without_ladder(self, world):
        db, queries, d = world
        clean = db.query(queries, d, backend="pallas")
        broker = db.broker(backend="pallas",
                           retry=RetryPolicy(degrade_after=1, **_FAST))
        spec = faults.FaultSpec("engine.dispatch", "resource_exhausted",
                                times=2)
        with faults.active(faults.FaultPlan([spec])):
            t = broker.submit(queries, d)
            res = t.result()
        _assert_identical(res, clean)      # same backend: exact bytes
        assert t.health.retries == 2
        assert t.health.backoff_seconds > 0
        assert not t.health.degradations   # transient: no ladder step

    def test_persistent_pallas_failure_walks_full_ladder(self, world, base):
        db, queries, d = world
        broker = db.broker(
            backend="pallas",
            retry=RetryPolicy(max_attempts=8, degrade_after=1, **_FAST))
        spec = faults.FaultSpec("engine.dispatch", "error", times=None,
                                match={"use_pallas": True})
        with faults.active(faults.FaultPlan([spec])):
            t = broker.submit(queries, d)
            res = t.result()
        _assert_same_rows(res, base, "ladder")
        stages = [(g.stage, g.after) for g in t.health.degradations]
        assert stages == [("compaction", "pallas/dense"),
                          ("backend", "jnp/dense")]
        assert res.degraded and t.health.degraded

    def test_retry_exhaustion_fails_structured_and_releases(self, world):
        db, queries, d = world
        broker = db.broker(backend="jnp",
                           retry=RetryPolicy(max_attempts=2, **_FAST),
                           max_inflight_interactions=10**9)
        spec = faults.FaultSpec("engine.dispatch", "error", times=None)
        with faults.active(faults.FaultPlan([spec])):
            t = broker.submit(queries, d)
            with pytest.raises(faults.InjectedKernelError):
                t.result()
        assert t.state == "error"
        assert t.health.attempts[0] == 2
        assert broker.inflight_interactions == 0   # budget fully released
        assert broker.errored == 1
        # backpressure slot is free again: a new submit is admitted
        t2 = broker.submit(queries, d)
        assert t2.result() is not None

    def test_straggler_speculative_reissue(self, world, base):
        db, queries, d = world
        broker = db.broker(
            backend="jnp",
            retry=RetryPolicy(straggler_slack=2.0,
                              straggler_min_timeout=0.02, **_FAST))
        spec = faults.FaultSpec("engine.dispatch", "delay", times=1,
                                delay=0.5)
        with faults.active(faults.FaultPlan([spec])):
            t = broker.submit(queries, d)
            res = t.result()
        _assert_identical(res, base)
        assert t.health.stragglers_reissued >= 1

    def test_capacity_error_not_retried(self, adversarial):
        entries, queries, d = adversarial
        pol = ExecutionPolicy(capacity=16, max_capacity_retries=0,
                              batching="periodic", batch_params={"s": 4},
                              num_bins=8)
        db = TrajectoryDB.from_segments(entries, policy=pol)
        broker = db.broker(backend="jnp", retry=RetryPolicy(**_FAST))
        t = broker.submit(queries, d)
        with pytest.raises(CapacityError):
            t.result()
        assert t.health.attempts[0] == 1    # permanent: no re-execution
        assert broker.inflight_interactions == 0

    def test_partial_result_after_error(self, world, base):
        db, queries, d = world
        broker = db.broker(backend="jnp")          # no retry: fail fast
        spec = faults.FaultSpec("engine.dispatch", "error", times=None,
                                after=1)           # group 0 clean, rest fail
        with faults.active(faults.FaultPlan([spec])):
            t = broker.submit(queries, d, group_size=1)
            with pytest.raises(faults.InjectedKernelError):
                t.result()
        assert t.num_groups > 1 and t.groups_completed == 1
        part = t.partial_result()
        assert part.degraded
        assert 0 < len(part.entry_idx) < len(base.entry_idx)
        # the delivered prefix is canonical: a subset of the clean rows
        rows = set(zip(base.entry_idx.tolist(), base.query_idx.tolist()))
        got = set(zip(part.entry_idx.tolist(), part.query_idx.tolist()))
        assert got < rows
        # a done ticket's partial_result is exactly result()
        t2 = broker.submit(queries, d)
        full = t2.result()
        assert t2.partial_result() is full and not full.degraded


class TestShardFaults:
    def test_pod_dropout_reroutes_to_single_device(self, world, base):
        db, queries, d = world
        broker = db.broker(backend="shard", retry=RetryPolicy(**_FAST))
        spec = faults.FaultSpec("shard.pod", "pod_dropout", times=1)
        with faults.active(faults.FaultPlan([spec])) as plan:
            t = broker.submit(queries, d)
            res = t.result()
        assert any(e.kind == "pod_dropout" for e in plan.events)
        _assert_same_rows(res, base, "reroute")
        assert res.degraded
        stages = [g.stage for g in t.health.degradations]
        assert stages == ["route"]
        assert t.health.degradations[0].after == "single-device"

    def test_pod_dropout_without_retry_is_structured(self, world):
        db, queries, d = world
        broker = db.broker(backend="shard")
        spec = faults.FaultSpec("shard.pod", "pod_dropout", times=None)
        with faults.active(faults.FaultPlan([spec])):
            t = broker.submit(queries, d)
            with pytest.raises(PodFailedError):
                t.result()
        assert broker.inflight_interactions == 0

    def test_shard_corrupt_count_byte_identical(self, world):
        db, queries, d = world
        clean = db.query(queries, d, backend="shard")
        spec = faults.FaultSpec("shard.count", "corrupt_count", times=None,
                                factor=4.0, bias=7)
        with faults.active(faults.FaultPlan([spec])):
            res = db.query(queries, d, backend="shard")
        _assert_identical(res, clean, "shard corrupt")


class TestPlanAndCacheFaults:
    def test_plan_failure_steps_pruning_ladder(self, world, base):
        db, queries, d = world
        pol = db.policy.with_(pruning="hierarchical")
        broker = db.broker(backend="jnp", policy=pol,
                           retry=RetryPolicy(**_FAST))
        spec = faults.FaultSpec("broker.plan", "error", times=1)
        with faults.active(faults.FaultPlan([spec])):
            t = broker.submit(queries, d)
            res = t.result()
        _assert_identical(res, base)
        degr = t.health.degradations
        assert [g.stage for g in degr] == ["pruning"]
        assert (degr[0].before, degr[0].after) == ("hierarchical", "spatial")
        assert res.degraded

    def test_plan_failure_without_retry_raises(self, world):
        db, queries, d = world
        broker = db.broker(backend="jnp")
        spec = faults.FaultSpec("broker.plan", "error", times=1)
        with faults.active(faults.FaultPlan([spec])):
            with pytest.raises(faults.InjectedKernelError):
                broker.submit(queries, d)

    def test_cache_faults_degrade_to_miss(self, world, base):
        db, queries, d = world
        broker = db.broker(backend="jnp", cache=SliceCache(),
                           retry=RetryPolicy(**_FAST))
        plan = faults.FaultPlan([
            faults.FaultSpec("cache.lookup", "error", times=1),
            faults.FaultSpec("cache.insert", "error", times=1)])
        with faults.active(plan):
            t = broker.submit(queries, d)
            res = t.result()
        _assert_identical(res, base)
        assert not res.degraded            # canonical path, just uncached
        assert broker.cache_failures == 2
        assert t.health.cache_failures == 1
        # cache survives: the next round trips lookup+insert cleanly
        t2 = broker.submit(queries, d)
        _assert_identical(t2.result(), base)
        t3 = broker.submit(queries, d)
        assert t3.done()                   # served from cache at submit
        _assert_identical(t3.result(), base)


class TestSchedulerFaults:
    def test_worker_failure_reissued(self, world, base):
        db, queries, d = world
        spec = faults.FaultSpec("scheduler.worker", "error", times=1)
        with faults.active(faults.FaultPlan([spec])):
            res, stats = db.query_stream(queries, d, backend="jnp")
        _assert_identical(res, base)
        assert stats.failures == 1
        assert stats.reissued >= 1

    def test_worker_failure_bounded(self, world):
        db, queries, d = world
        spec = faults.FaultSpec("scheduler.worker", "error", times=None)
        with faults.active(faults.FaultPlan([spec])):
            with pytest.raises(faults.InjectedKernelError):
                db.query_stream(queries, d, backend="jnp")


# ----------------------------------------------------------------------
# Whole-plan determinism: the same seeded plan replays identically.
# ----------------------------------------------------------------------
def test_chaos_run_replays_bit_identically(world, base):
    db, queries, d = world

    def run(seed):
        plan = faults.FaultPlan(
            [faults.FaultSpec("engine.dispatch", "error", times=None,
                              probability=0.4),
             faults.FaultSpec("engine.count", "corrupt_count", times=None,
                              probability=0.3, factor=6.0)], seed=seed)
        broker = db.broker(backend="jnp",
                           retry=RetryPolicy(max_attempts=16, **_FAST))
        with faults.active(plan):
            t = broker.submit(queries, d)
            res = t.result()
        return res, [(e.site, e.kind, e.index) for e in plan.events], t
    res_a, ev_a, ta = run(5)
    res_b, ev_b, tb = run(5)
    assert ev_a == ev_b and ev_a          # same faults fired, same order
    assert ta.health.retries == tb.health.retries
    _assert_identical(res_a, base)
    _assert_identical(res_b, base)
