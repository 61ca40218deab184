"""Quickstart: distance-threshold queries through the ``repro.api`` facade.

Walkthrough
-----------
1.  ``TrajectoryDB.from_scenario`` builds one of the paper's §7.2 datasets
    (here S2: GALAXY, d=5), sorts the entry segments by ``t_start`` and
    constructs the temporal-bin index (§4).  The scenario's query workload
    rides along as ``db.scenario_queries`` / ``db.scenario_d``.
2.  ``db.query(queries, d)`` is the single entrypoint: it sorts the queries
    internally, plans batches with the policy's algorithm (§6 — PERIODIC
    here, the paper's practical recommendation), executes on the chosen
    backend, and maps result indices back to the *caller's* query order.
3.  Backends are pluggable: ``"jnp"`` (XLA oracle, the CPU default),
    ``"pallas"`` (the TPU kernel, interpret mode on CPU), ``"rtree"`` (the
    paper's §7.3 CPU baseline) and ``"brute"`` (all-pairs oracle) return
    identical canonical result sets — the cross-check below asserts it.

Run:  PYTHONPATH=src python examples/quickstart.py
(or ``pip install -e .`` once, then plain ``python examples/quickstart.py``)
"""
import numpy as np

from repro.api import ExecutionPolicy, TrajectoryDB
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. dataset + index: one constructor owns sorting and index construction
policy = ExecutionPolicy(batching="periodic", batch_params={"s": 64},
                         num_bins=1000)
db = TrajectoryDB.from_scenario("S2", scale=0.02, policy=policy)
queries, d = db.scenario_queries, db.scenario_d
print(f"database: {len(db)} entry segments;  query set: {len(queries)} "
      f"segments;  threshold d = {d}")

# 2. one entrypoint: plan + execute + caller-order results
result = db.query(queries, d, backend="jnp")
plan, stats = result.plan, result.stats
print(f"plan: {plan.num_batches} batches, "
      f"{plan.total_interactions:,} interactions "
      f"({plan.total_interactions / len(queries):.0f} per query)")
print(f"result set: {len(result)} (entry, query, interval) items in "
      f"{stats.total_seconds:.3f}s "
      f"({stats.total_interactions / max(stats.kernel_seconds, 1e-9) / 1e6:.0f}"
      f" M interactions/s)")

# 3. results speak the paper's §3 language: matched trajectories
print(f"trajectories within d of the search set: "
      f"{result.matched_trajectories()[:8]} ...")
for i in range(min(3, len(result))):
    print(f"  entry traj {result.entry_traj[i]} seg {result.entry_seg[i]} "
          f"within {d} of query segment {result.query_idx[i]} during "
          f"[{result.t_enter[i]:.2f}, {result.t_exit[i]:.2f}]")

# 4. pluggable backends, identical answers: cross-check vs the R-tree
#    baseline — same canonical rows, caller query order on both sides.
rt = db.query(queries, d, backend="rtree")
assert len(rt) == len(result), (len(rt), len(result))
np.testing.assert_array_equal(rt.entry_idx, result.entry_idx)
np.testing.assert_array_equal(rt.query_idx, result.query_idx)
print(f"R-tree baseline agrees: {len(rt)} items ✓")

# 5. streaming mode: the same query through the deadline/re-issue scheduler
#    (what a serving deployment runs — see repro.serve.trajectory).
stream_result, sched = db.query_stream(queries, d, backend="jnp")
assert len(stream_result) == len(result)
print(f"query_stream: {sched.completed} batches completed, "
      f"{sched.reissued} re-issued, wall {sched.wall_seconds:.3f}s ✓")
