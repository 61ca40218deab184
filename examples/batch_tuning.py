"""Batch-size tuning with the §8 response-time performance model.

Demonstrates the paper's headline workflow: benchmark the platform once
(T1/T2/T3 device curves + host fits), estimate α per temporal epoch for
the dataset, then let the model pick a PERIODIC batch size — and compare
against the measured optimum.  The dataset/query workload comes through
the ``repro.api`` facade; the perf model still speaks the engine-level
interface, obtained via ``db.engine()``.

A second section moves to the bimodal twin-swarm scenario C3 and tunes
the *pruning* knobs instead: bin-level MBRs see both clouds in every bin
and prune nothing, the hierarchical K-box index splits them, and the
``max_subranges`` budget decides how much of that split survives
planning.

Run:  PYTHONPATH=src python examples/batch_tuning.py
"""
import time

from repro.api import ExecutionPolicy, TrajectoryDB
from repro.compile_cache import enable_compile_cache
from repro.core.perfmodel import (ResponseTimeModel, benchmark_device_curves,
                                  benchmark_host_curves)

enable_compile_cache()

db = TrajectoryDB.from_scenario(
    "S5", scale=0.01,
    policy=ExecutionPolicy(batching="periodic", num_bins=1000))
queries, d = db.scenario_queries, db.scenario_d
engine = db.engine("jnp")          # perf-model interop surface

print("benchmarking device curves (T1/T2/T3 per interaction class) ...")
device = benchmark_device_curves(c_values=(256, 1024, 4096),
                                 q_values=(16, 64, 256), repeats=2)
print(f"  dispatch overhead Θ = {device.theta * 1e6:.0f} µs")

print("fitting host curves (invocation overhead + transfer) ...")
host = benchmark_host_curves(engine, queries, s_values=(16, 48, 128))
print(f"  T1_host(s) = {host.coef_a:.4f} · s^{host.coef_b:.2f}")

model = ResponseTimeModel(device, host, num_epochs=20)
candidates = (16, 32, 48, 64, 96, 128)
s_model, preds = model.pick_batch_size(engine, queries, d,
                                       candidates=candidates)
print(f"model picks s = {s_model}")
for p in preds:
    print(f"  s={p['s']:4d}  predicted {p['total_seconds'] * 1e3:8.1f} ms "
          f"({p['num_batches']} batches, ~{p['predicted_hits']:.0f} hits)")

print("measuring actual response times ...")
actual = {}
for s in candidates:
    db.query(queries, d, batching="periodic", s=s)       # warm the jit cache
    stats = db.query(queries, d, batching="periodic", s=s).stats
    actual[s] = stats.total_seconds
    print(f"  s={s:4d}  measured {actual[s] * 1e3:8.1f} ms")
s_best = min(actual, key=actual.get)
print(f"actual best s = {s_best}; model slowdown = "
      f"{100 * (actual[s_model] / actual[s_best] - 1):.1f}% "
      f"(paper Table 3: 0.1–6.3%)")

# ---------------------------------------------------------------------
# Pruning-mode tuning on the bimodal C3 scenario: a few large temporal
# bins (so each bin spans many kernel tiles), K = 4 boxes per bin to
# separate the two swarms, and a sub-range budget wide enough that the
# planner keeps the split instead of coalescing back to full bins.
print("\ntuning pruning on the bimodal twin-swarm scenario C3 ...")
db3 = TrajectoryDB.from_scenario(
    "C3", scale=0.02,
    policy=ExecutionPolicy(batching="periodic", batch_params={"s": 8},
                           num_bins=8, index_kboxes=4, max_subranges=64))
q3, d3 = db3.scenario_queries, db3.scenario_d


def timed(**kw):
    db3.query(q3, d3, **kw)                           # warm the jit cache
    t0 = time.perf_counter()
    res = db3.query(q3, d3, **kw)
    return time.perf_counter() - t0, res


for pruning in ("none", "spatial", "hierarchical"):
    sec, res = timed(pruning=pruning)
    st = res.stats
    print(f"  pruning={pruning:13s} {sec * 1e3:7.1f} ms  "
          f"dispatched={st.total_interactions:8d}  hits={st.total_hits}")

print("sweeping the max_subranges budget (hierarchical) ...")
for cap in (1, 4, 16, 64):
    sec, res = timed(pruning="hierarchical",
                     policy=db3.policy.with_(max_subranges=cap))
    st = res.stats
    print(f"  max_subranges={cap:3d} {sec * 1e3:7.1f} ms  "
          f"dispatched={st.total_interactions:8d}  hits={st.total_hits}")
