"""Spans and counters inside ``TrajectoryDB.query`` (``repro.core.spans``):
what one call records on ``ExecStats.span_seconds`` / ``ExecStats.counts``,
that the numbers add up, that concurrent queries keep them apart, and that
a recording profiler sees the spans."""
import glob
import os
import threading

import jax
import numpy as np
import pytest

import repro
from repro.core import spans

#: Every leaf span of a ``db.query`` on an engine backend whose batches
#: overflow their first capacity.
LEAVES = ("repro.facade.sort", "repro.plan.batching", "repro.plan.prune",
          "repro.plan.refine", "repro.engine.dispatch", "repro.exec.sync",
          "repro.exec.retry", "repro.engine.fetch", "repro.exec.marshal",
          "repro.exec.concat", "repro.facade.canonical")
#: Spans that hold other spans.
PARENTS = ("repro.query", "repro.plan", "repro.exec.run", "repro.exec.group")
#: d at which 4 of S1's 25 batches overflow their 256-slot capacity.
D_OVERFLOW = 6.0


@pytest.fixture(scope="module")
def db():
    pol = repro.ExecutionPolicy(batching="periodic", batch_params={"s": 16},
                                num_bins=100, capacity=256)
    return repro.TrajectoryDB.from_scenario("S1", scale=0.01, policy=pol)


@pytest.fixture(scope="module")
def overflowing(db):
    db.query(db.scenario_queries, D_OVERFLOW, backend="jnp")   # compiles
    return db.query(db.scenario_queries, D_OVERFLOW, backend="jnp")


def _leaf_seconds(st) -> float:
    # repro.exec.marshal holds repro.engine.fetch.
    return sum(v for k, v in st.span_seconds.items()
               if k in LEAVES and k != "repro.engine.fetch")


def test_query_records_every_leaf_span(overflowing):
    st = overflowing.stats
    assert set(st.span_seconds) == set(LEAVES) | set(PARENTS)
    assert all(v > 0 for v in st.span_seconds.values())


@pytest.mark.parametrize("pipeline", [True, False])
def test_leaves_add_up_to_no_more_than_the_wall(db, pipeline):
    r = db.query(db.scenario_queries, D_OVERFLOW, backend="jnp",
                 pipeline=pipeline)
    st = r.stats
    wall = st.span_seconds["repro.query"]
    assert _leaf_seconds(st) <= wall
    assert st.span_seconds["repro.engine.fetch"] <= (
        st.span_seconds["repro.exec.marshal"])
    assert st.span_seconds["repro.exec.run"] == st.total_seconds <= wall
    # plan_seconds keeps its interval: the batching algorithm's own time
    # and refinement, inside the whole planning span.
    assert st.plan_seconds == pytest.approx(
        r.plan.batch_plan.plan_seconds + st.span_seconds["repro.plan.refine"])
    assert st.plan_seconds <= st.span_seconds["repro.plan"]


@pytest.mark.parametrize("pipeline", [True, False])
def test_dispatch_and_sync_seconds_are_their_spans(db, pipeline):
    st = db.query(db.scenario_queries, D_OVERFLOW, backend="jnp",
                  pipeline=pipeline).stats
    assert st.dispatch_seconds == st.span_seconds["repro.engine.dispatch"]
    assert st.sync_seconds == st.span_seconds["repro.exec.sync"]
    assert st.dispatch_seconds > 0 and st.sync_seconds > 0
    if pipeline:
        assert st.kernel_seconds == st.sync_seconds
        assert all(b.kernel_seconds == 0 for b in st.batches)
    else:
        # Per batch: its first dispatch and its wait.
        assert st.kernel_seconds == pytest.approx(
            st.dispatch_seconds + st.sync_seconds)
    assert st.retry_seconds == pytest.approx(
        st.span_seconds["repro.exec.retry"])


def test_result_rows_are_the_result(overflowing):
    st = overflowing.stats
    assert st.counts["result_rows"] == len(overflowing) == st.total_hits
    live = [b for b in overflowing.plan.batches if b.num_candidates]
    assert st.counts["dispatches"] == len(live)


@pytest.mark.parametrize("pipeline", [True, False])
def test_retried_dispatches_count_the_overflowed_batches(db, pipeline):
    st = db.query(db.scenario_queries, D_OVERFLOW, backend="jnp",
                  pipeline=pipeline).stats
    retried = sum(1 for b in st.batches if b.retries > 0)
    assert retried > 0
    assert st.counts["retried_dispatches"] == retried


def test_no_retry_no_retry_span(db):
    st = db.query(db.scenario_queries, db.scenario_d, backend="jnp").stats
    assert "repro.exec.retry" not in st.span_seconds
    assert "retried_dispatches" not in st.counts


def test_h2d_bytes_are_the_plan_slices(overflowing):
    """Every dispatch, retries included, uploads its candidate and query
    slices (8 float32 columns a row) and the threshold (one float32)."""
    st, plan = overflowing.stats, overflowing.plan
    row = 8 * np.dtype(np.float32).itemsize
    want = sum((b.num_candidates * row + b.size * row + 4) * (1 + s.retries)
               for b, s in zip(plan.batches, st.batches)
               if b.num_candidates)
    assert st.counts["h2d_bytes"] == want


def test_result_slots_are_the_capacities_copied_back(overflowing):
    st, plan = overflowing.stats, overflowing.plan
    # A retried batch comes back at its retry capacity.
    assert st.counts["result_slots"] >= sum(
        c for b, c in zip(plan.batches, plan.capacities) if b.num_candidates)
    assert st.counts["result_rows"] <= st.counts["result_slots"]


def test_threads_keep_their_own_recorders(db):
    queries = db.scenario_queries
    half = queries.take(np.arange(len(queries) // 2))
    solo = {n: db.query(q, D_OVERFLOW, backend="jnp").stats.counts
            for n, q in (("all", queries), ("half", half))}
    out, errors = {}, []
    barrier = threading.Barrier(2)

    def run(name, q):
        try:
            barrier.wait()
            for _ in range(3):
                r = db.query(q, D_OVERFLOW, backend="jnp")
                out.setdefault(name, []).append(r)
        except Exception as e:           # surfaced by the assert below
            errors.append(e)

    threads = [threading.Thread(target=run, args=a)
               for a in (("all", queries), ("half", half))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for name, results in out.items():
        for r in results:
            assert r.stats.counts == solo[name]
            assert r.stats.counts["result_rows"] == len(r)
    recorders = {id(r.stats.span_seconds) for rs in out.values() for r in rs}
    assert len(recorders) == 6
    assert spans.current() is None


def test_recorder_is_joined_not_nested():
    with spans.recording() as outer:
        with spans.recording() as inner:
            spans.count("x", 2)
            with spans.span("repro.test"):
                pass
        assert inner is outer
    assert outer.counts == {"x": 2} and "repro.test" in outer.seconds
    assert spans.current() is None
    spans.count("x")                      # no recorder: a no-op
    with spans.span("repro.test") as sp:  # no recorder: timed all the same
        pass
    assert sp.seconds >= 0


def test_a_recording_profiler_sees_the_spans(db, tmp_path):
    from jax.profiler import ProfileData
    db.query(db.scenario_queries, db.scenario_d, backend="jnp")
    jax.profiler.start_trace(str(tmp_path))
    try:
        db.query(db.scenario_queries, db.scenario_d, backend="jnp")
        with spans.span("repro.bare"):
            pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("repro.")]
    names = {n for n, _ in events}
    assert {"repro.query", "repro.plan", "repro.engine.dispatch",
            "repro.exec.sync", "repro.facade.canonical",
            "repro.bare"} <= names
    # One request's spans share its qid; a span outside a query has none.
    qids = {a.get("qid") for n, a in events if n != "repro.bare"}
    assert len(qids) == 1 and None not in qids
    assert all("qid" not in a for n, a in events if n == "repro.bare")
    dispatch = next(a for n, a in events if n == "repro.engine.dispatch")
    assert {"candidates", "queries", "capacity"} <= set(dispatch)
