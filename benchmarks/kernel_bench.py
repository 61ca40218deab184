"""§5 kernel microbenchmark + compaction/executor comparison.

Four sections:

* ``run``            — interaction-tile throughput vs tile shape (jnp path
                       plus a Pallas interpret-mode parity point).
* ``run_compaction`` — ``ops.query_block`` with ``compaction="dense"`` (two
                       passes: dense mask and interval planes, then an XLA
                       cumsum/scatter) vs ``compaction="fused"`` (PR 2's
                       in-kernel compaction), both through the Pallas
                       kernel so the comparison isolates the compaction
                       strategy.
* ``run_executor``   — end-to-end S2 scenario through the facade: the
                       per-batch-sync loop vs the async pipelined executor,
                       for both compaction strategies (engine backends).
* ``run_executor_sharded`` — the same S2 scenario through
                       ``backend="shard"`` (the PR 3 temporal-pod mesh
                       backend), sync vs pipelined, plus a grouped-dispatch
                       row (``group_size``) exercising the marshalling/
                       compute overlap.

``canonical_report`` bundles the first three into the BENCH_PR2 dict
(``BENCH_PR2.json`` — the perf-trajectory baseline).
``canonical_report_pr3`` re-runs the S2 executor rows and adds the sharded
section — ``benchmarks/run.py --only bench_pr3`` writes it as
``BENCH_PR3.json`` and prints the regression comparison against
``BENCH_PR2.json``.

Run directly::

    PYTHONPATH=src python -m benchmarks.kernel_bench [--quick] [--json PATH]
                                                     [--pr3 PATH]
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmarks.common import timed
from repro.kernels import ops


def _random_packed(rng, n):
    ts = rng.uniform(0, 50, n).astype(np.float32)
    out = np.zeros((n, 8), np.float32)
    out[:, 0:3] = rng.uniform(0, 30, (n, 3))
    out[:, 3:6] = out[:, 0:3] + rng.normal(0, 2, (n, 3))
    out[:, 6] = ts
    out[:, 7] = ts + rng.uniform(0.1, 3, n)
    return out


def run(shapes=((1024, 64), (4096, 64), (4096, 256), (16384, 128)),
        repeats: int = 3) -> list[dict]:
    rng = np.random.default_rng(0)
    rows = []
    for c, q in shapes:
        e = _random_packed(rng, c)
        qq = _random_packed(rng, q)
        d = np.float32(3.0)
        ops.count_hits(e, qq, d, use_pallas=False).block_until_ready()
        _, sec = timed(lambda: ops.count_hits(e, qq, d, use_pallas=False)
                       .block_until_ready(), repeats=repeats)
        rows.append({"bench": "kernel", "impl": "jnp", "c": c, "q": q,
                     "us_per_call": sec * 1e6,
                     "interactions_per_s": c * q / sec})
    # Pallas interpret-mode parity point (small shape)
    c, q = 512, 64
    e, qq = _random_packed(rng, c), _random_packed(rng, q)
    d = np.float32(3.0)
    ops.count_hits(e, qq, d, use_pallas=True, cand_blk=128,
                   qry_blk=64).block_until_ready()
    _, sec = timed(lambda: ops.count_hits(e, qq, d, use_pallas=True,
                                          cand_blk=128, qry_blk=64)
                   .block_until_ready(), repeats=1)
    rows.append({"bench": "kernel", "impl": "pallas-interpret", "c": c,
                 "q": q, "us_per_call": sec * 1e6,
                 "interactions_per_s": c * q / sec})
    return rows


def run_compaction(shapes=((512, 64), (1024, 128)), repeats: int = 3,
                   capacity: int = 4096) -> list[dict]:
    """Dense two-phase vs fused in-kernel compaction (Pallas path)."""
    import jax
    rng = np.random.default_rng(1)
    rows = []
    for c, q in shapes:
        e = _random_packed(rng, c)
        qq = _random_packed(rng, q)
        d = np.float32(3.0)
        for compaction in ("dense", "fused"):
            def call(compaction=compaction):
                return jax.block_until_ready(ops.query_block(
                    e, qq, d, capacity=capacity, use_pallas=True,
                    cand_blk=128, qry_blk=64, compaction=compaction))
            out = call()                                   # warm jit
            _, sec = timed(call, repeats=repeats)
            rows.append({"bench": "compaction", "impl": compaction,
                         "c": c, "q": q, "hits": int(out["count"]),
                         "us_per_call": sec * 1e6,
                         "interactions_per_s": c * q / sec})
    return rows


def run_executor(scale: float = 0.01, s: int = 32,
                 repeats: int = 2) -> list[dict]:
    """End-to-end S2: sync vs pipelined executor × dense vs fused."""
    from repro.api import ExecutionPolicy, TrajectoryDB
    policy = ExecutionPolicy(batching="periodic", batch_params={"s": s},
                             num_bins=500)
    db = TrajectoryDB.from_scenario("S2", scale=scale, policy=policy)
    queries, d = db.scenario_queries, db.scenario_d
    combos = [("jnp", "dense", False), ("jnp", "dense", True),
              ("pallas", "dense", False), ("pallas", "dense", True),
              ("pallas", "fused", False), ("pallas", "fused", True)]
    rows = []
    for backend, compaction, pipeline in combos:
        def call(backend=backend, compaction=compaction, pipeline=pipeline):
            return db.query(queries, d, backend=backend,
                            compaction=compaction, pipeline=pipeline)
        call()                                              # warm jit
        # Keep wall time and stats from the SAME (best) run, so the
        # kernel/host split in the canonical report is self-consistent.
        runs = [timed(call, repeats=1) for _ in range(repeats)]
        res, sec = min(runs, key=lambda r: r[1])
        st = res.stats
        rows.append({
            "bench": "executor", "scenario": "S2", "scale": scale,
            "backend": backend, "compaction": compaction,
            "pipeline": pipeline, "total_seconds": sec,
            "kernel_seconds": st.kernel_seconds,
            "host_seconds": max(sec - st.kernel_seconds, 0.0),
            "interactions_per_s": st.total_interactions / sec,
            "num_invocations": st.num_invocations,
            "num_syncs": st.num_syncs, "total_hits": st.total_hits,
        })
    return rows


def run_executor_sharded(scale: float = 0.01, s: int = 32,
                         repeats: int = 2) -> list[dict]:
    """End-to-end S2 through ``backend="shard"``: sync vs pipelined vs
    grouped pipelined dispatch on the local temporal-pod mesh."""
    import jax
    from repro.api import ExecutionPolicy, TrajectoryDB
    policy = ExecutionPolicy(batching="periodic", batch_params={"s": s},
                             num_bins=500)
    db = TrajectoryDB.from_scenario("S2", scale=scale, policy=policy)
    queries, d = db.scenario_queries, db.scenario_d
    pods = len(jax.devices())
    combos = [(False, None), (True, None), (True, 4)]
    rows = []
    for pipeline, group_size in combos:
        pol = policy.with_(pipeline=pipeline, group_size=group_size)

        def call(pol=pol):
            return db.query(queries, d, backend="shard", policy=pol)
        call()                                              # warm jit
        runs = [timed(call, repeats=1) for _ in range(repeats)]
        res, sec = min(runs, key=lambda r: r[1])
        st = res.stats
        rows.append({
            "bench": "executor_sharded", "scenario": "S2", "scale": scale,
            "backend": "shard", "pods": pods, "pipeline": pipeline,
            "group_size": group_size, "total_seconds": sec,
            "interactions_per_s": st.total_interactions / sec,
            "num_invocations": st.num_invocations,
            "num_groups": st.num_groups, "num_syncs": st.num_syncs,
            "total_hits": st.total_hits,
        })
    return rows


def canonical_report(*, quick: bool = False) -> dict:
    """The BENCH_PR2 payload: one dict, JSON-serializable, regressable."""
    scale = 0.005 if quick else 0.01
    kernel = run(shapes=(((1024, 64), (4096, 64)) if quick else
                         ((1024, 64), (4096, 64), (4096, 256), (16384, 128))),
                 repeats=1 if quick else 3)
    compaction = run_compaction(
        shapes=((512, 64),) if quick else ((512, 64), (1024, 128)),
        repeats=1 if quick else 3)
    executor = run_executor(scale=scale, repeats=1 if quick else 2)
    return {"bench": "BENCH_PR2", "scenario": "S2", "scale": scale,
            "quick": quick, "kernel": kernel, "compaction": compaction,
            "executor": executor}


def canonical_report_pr3(*, quick: bool = False) -> dict:
    """The BENCH_PR3 payload: the S2 executor rows re-run on this tree
    (regressable 1:1 against BENCH_PR2.json's ``executor`` section) plus
    the sharded-executor section."""
    scale = 0.005 if quick else 0.01
    repeats = 1 if quick else 3        # best-of-3: the S2 rows are short
    return {"bench": "BENCH_PR3", "scenario": "S2", "scale": scale,
            "quick": quick, "baseline": "BENCH_PR2.json",
            "executor": run_executor(scale=scale, repeats=repeats),
            "sharded_executor": run_executor_sharded(scale=scale,
                                                     repeats=repeats)}


def compare_executor_sections(pr3: dict, pr2: dict,
                              label: str | None = None) -> list[str]:
    """Per-combo interactions/sec ratio of a report's S2 executor rows vs a
    baseline report (same scenario/scale keys only).  > 1.0 means faster.
    ``label`` defaults to ``executor_vs_<baseline bench name>``."""
    if label is None:
        suffix = pr2.get("bench", "baseline").replace("BENCH_", "").lower()
        label = f"executor_vs_{suffix}"
    if pr2.get("scale") != pr3.get("scale"):
        return [f"# baseline scale {pr2.get('scale')} != {pr3.get('scale')}"
                " — no comparison"]
    base = {(r["backend"], r["compaction"], r["pipeline"]):
            r["interactions_per_s"] for r in pr2.get("executor", [])}
    lines = []
    for r in pr3.get("executor", []):
        key = (r["backend"], r["compaction"], r["pipeline"])
        if key not in base or not base[key]:
            continue
        ratio = r["interactions_per_s"] / base[key]
        lines.append(
            f"{label},{key[0]},compaction={key[1]},"
            f"pipeline={key[2]},ratio={ratio:.2f}")
    return lines


def print_kernel_rows(rows: list[dict]) -> None:
    for r in rows:
        print(f"kernel,{r['impl']},c={r['c']},q={r['q']},"
              f"us_per_call={r['us_per_call']:.0f},"
              f"Minter_per_s={r['interactions_per_s'] / 1e6:.1f}")


def print_compaction_rows(rows: list[dict]) -> None:
    for r in rows:
        print(f"compaction,{r['impl']},c={r['c']},q={r['q']},"
              f"hits={r['hits']},us_per_call={r['us_per_call']:.0f},"
              f"Minter_per_s={r['interactions_per_s'] / 1e6:.1f}")


def print_executor_rows(rows: list[dict]) -> None:
    for r in rows:
        print(f"executor,{r['backend']},compaction={r['compaction']},"
              f"pipeline={r['pipeline']},total_s={r['total_seconds']:.3f},"
              f"kernel_s={r['kernel_seconds']:.3f},"
              f"syncs={r['num_syncs']}/{r['num_invocations']},"
              f"Minter_per_s={r['interactions_per_s'] / 1e6:.1f}")


def print_sharded_rows(rows: list[dict]) -> None:
    for r in rows:
        print(f"executor_sharded,shard,pods={r['pods']},"
              f"pipeline={r['pipeline']},groups={r['num_groups']},"
              f"total_s={r['total_seconds']:.3f},"
              f"syncs={r['num_syncs']}/{r['num_invocations']},"
              f"Minter_per_s={r['interactions_per_s'] / 1e6:.1f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (seconds, not minutes)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the canonical BENCH_PR2 report to PATH")
    ap.add_argument("--pr3", default=None, metavar="PATH",
                    help="also write the BENCH_PR3 report (S2 executor + "
                         "sharded-executor sections) to PATH")
    args = ap.parse_args(argv)

    report = canonical_report(quick=args.quick)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"# wrote {args.json}")
    print_kernel_rows(report["kernel"])
    print_compaction_rows(report["compaction"])
    print_executor_rows(report["executor"])
    if args.pr3:
        pr3 = canonical_report_pr3(quick=args.quick)
        with open(args.pr3, "w") as f:
            json.dump(pr3, f, indent=2)
        print(f"# wrote {args.pr3}")
        print_executor_rows(pr3["executor"])
        print_sharded_rows(pr3["sharded_executor"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
