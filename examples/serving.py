"""Session-oriented serving with the QueryBroker (PR 4).

Demonstrates the full serving loop on a scaled-down paper scenario:

* ticketed async submit — ``submit()`` returns a ``QueryTicket`` handle,
  nothing executes until the pump runs;
* incremental delivery — ``step()`` executes one dispatch group per call
  (≤ 2 host syncs each) and ``on_slice`` / ``partial()`` expose results as
  they marshal;
* §8-model admission — tickets carry predicted execution times, deadlines
  are priced at submit, and an in-flight-interactions budget applies
  backpressure;
* shard routing — the same submit/pump flow over ``backend="shard"``
  (per-pod fan-out via the PodRouter, one pod per local device).

Run: ``PYTHONPATH=src python examples/serving.py``
"""
import numpy as np

from repro.api import AdmissionError, TrajectoryDB
from repro.compile_cache import enable_compile_cache

enable_compile_cache()


def main():
    db = TrajectoryDB.from_scenario("S2", scale=0.01)
    queries, d = db.scenario_queries, db.scenario_d
    print(f"db: {len(db)} segments, workload: {len(queries)} query segments")

    # ------------------------------------------------------------------
    # 1. Ticketed submit + incremental pump.
    # ------------------------------------------------------------------
    broker = db.broker(backend="jnp")
    ticket = broker.submit(
        queries, d, group_size=2,
        on_slice=lambda tk, sl: print(
            f"  slice {sl.group_index + 1}/{sl.num_groups}: "
            f"{len(sl.result)} rows, {sl.num_syncs} host syncs, "
            f"{sl.seconds * 1e3:.1f} ms"))
    print(f"\nsubmitted ticket {ticket.uid}: state={ticket.state}, "
          f"{ticket.num_groups} dispatch groups, "
          f"{ticket.interactions} interactions")
    while broker.step():                       # the serving event loop
        print(f"  partial() now holds {len(ticket.partial())} rows")
    result = ticket.result()
    print(f"ticket {ticket.uid} done: {len(result)} rows, "
          f"{result.matched_trajectories().size} matched trajectories")

    # sanity: identical to the one-shot query path
    assert np.array_equal(result.entry_idx,
                          db.query(queries, d).entry_idx)

    # ------------------------------------------------------------------
    # 2. Model-priced admission + deadlines + backpressure.
    # ------------------------------------------------------------------
    # A crude §8-style predictor (fit a real one with repro.core.perfmodel)
    predict = lambda batch: 50e-9 * batch.num_ints
    priced = db.broker(backend="jnp", predict_seconds=predict,
                       max_inflight_interactions=2 * ticket.interactions)
    t1 = priced.submit(queries, d, deadline=30.0)
    print(f"\nadmitted ticket {t1.uid}: predicted "
          f"{t1.predicted_seconds * 1e3:.2f} ms against a 30 s deadline")
    try:
        priced.submit(queries, d, deadline=t1.predicted_seconds / 100)
    except AdmissionError as e:
        print(f"rejected at admission (deadline unmeetable): {e}")
    try:
        priced.submit(queries, d)
        priced.submit(queries, d)              # budget is 2 tickets' worth
    except AdmissionError as e:
        print(f"rejected by backpressure: {e}")
    priced.run_until_idle()
    print(f"after pumping: {priced.completed} completed, "
          f"{priced.rejected} rejected, inflight="
          f"{priced.inflight_interactions}")

    # ------------------------------------------------------------------
    # 3. The same flow over the sharded mesh backend.
    # ------------------------------------------------------------------
    shard = db.broker(backend="shard")
    ts = shard.submit(queries, d, group_size=2)
    ts.result()
    rt = ts.routing
    print(f"\nshard ticket {ts.uid}: {rt.num_pods} pod(s), "
          f"mean {rt.mean_pods_per_batch:.1f} pods per batch, "
          f"per-pod hits {rt.pod_hits.tolist()} "
          f"(max/mean balance {rt.hit_balance:.2f})")
    print("\nOK — serving demo complete")


if __name__ == "__main__":
    main()
