"""Benchmark aggregator: one harness per paper table/figure.

Prints one CSV block per benchmark.  Run as::

    PYTHONPATH=src python -m benchmarks.run [--full]

``--full`` uses larger dataset scales (minutes on CPU); the default keeps
each benchmark to seconds so CI can execute the whole harness.

The ``bench_pr2`` entry additionally writes the canonical
``BENCH_PR2.json`` (see ``benchmarks.kernel_bench.canonical_report``) —
the first point of the perf trajectory: interactions/sec, kernel vs host
seconds, dense vs fused compaction and sync vs pipelined execution on the
S2 scenario.  Future PRs regress against it (``--bench-out`` moves the
file; CI uploads it as a workflow artifact).

The ``bench_pr3`` entry writes ``BENCH_PR3.json``: the same S2 executor
rows re-run on this tree plus the PR 3 sharded-executor section
(``backend="shard"`` sync / pipelined / grouped dispatch), and prints the
per-combo interactions/sec ratio against the ``BENCH_PR2.json`` baseline
when that file is present.

The ``bench_pr4`` entry writes ``BENCH_PR4.json`` (see
``benchmarks.broker_bench``): the S2 executor rows again (ratioed against
``BENCH_PR3.json``), the serving comparison (sequential ``db.query`` vs
``TrajectoryQueryService.drain()`` vs the ``QueryBroker`` pump, with
per-request latency distributions and time-to-first-slice) and the
sharded-routing section (pod-partition balance time vs num_ints).

The ``bench_pr5`` entry writes ``BENCH_PR5.json`` (see
``benchmarks.prune_bench``): the S2 executor rows again (ratioed against
``BENCH_PR4.json``), the spatiotemporal-pruning comparison on the
clustered C1 scenario (pruning on vs off: wall, interactions, pruned-tile
fraction, speedup) and the spatial-selectivity sweep over ``d``.

The ``bench_pr6`` entry writes ``BENCH_PR6.json`` (see
``benchmarks.lint_bench``): ``repro.lint`` wall time over ``src/`` and the
full tree (files, KLoC/s, violation counts) plus the CLI end-to-end time,
checked against the 5 s CI budget.

The ``bench_pr7`` entry writes ``BENCH_PR7.json`` (see
``benchmarks.prune_bench.canonical_report_pr7``): the S2 executor rows
again (ratioed against ``BENCH_PR5.json``) plus the pruning-mode matrix
(none / spatial / hierarchical × jnp / pallas) on the clustered C1 and
bimodal twin-swarm C3 scenarios — the hierarchical K-box index with
device-side live-tile dispatch vs the PR 5 bin-level pruner.

The ``bench_pr8`` entry writes ``BENCH_PR8.json`` (see
``benchmarks.shard_bench.canonical_report_pr8``): the S2 executor rows
again (ratioed against ``BENCH_PR7.json``), the sparse-vs-dense shard
dispatch matrix on C3 (spatial vs pod-local hierarchical planning ×
dense vs sparse routed execution, with pods-skipped accounting) and the
repeated-sensor result-cache section (broker with vs without a
``SliceCache``).

The ``bench_pr10`` entry writes ``BENCH_PR10.json`` (see
``benchmarks.fault_bench.canonical_report_pr10``): the S2 executor rows
re-run with all fault-injection hooks present but disarmed (ratioed
against ``BENCH_PR8.json`` — the < 2 % hook-overhead gate) plus the
broker recovery-latency section (clean vs one injected kernel failure
vs one dropped pod, all verified row-for-row against the clean run).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--bench-out", default="BENCH_PR2.json",
                    help="path for the canonical bench_pr2 JSON report")
    ap.add_argument("--bench-out3", default="BENCH_PR3.json",
                    help="path for the bench_pr3 JSON report")
    ap.add_argument("--bench-out4", default="BENCH_PR4.json",
                    help="path for the bench_pr4 JSON report")
    ap.add_argument("--bench-out5", default="BENCH_PR5.json",
                    help="path for the bench_pr5 JSON report")
    ap.add_argument("--bench-out6", default="BENCH_PR6.json",
                    help="path for the bench_pr6 JSON report")
    ap.add_argument("--bench-out7", default="BENCH_PR7.json",
                    help="path for the bench_pr7 JSON report")
    ap.add_argument("--bench-out8", default="BENCH_PR8.json",
                    help="path for the bench_pr8 JSON report")
    ap.add_argument("--baseline", default="BENCH_PR2.json",
                    help="baseline report bench_pr3 compares against")
    ap.add_argument("--baseline4", default="BENCH_PR3.json",
                    help="baseline report bench_pr4 compares against")
    ap.add_argument("--baseline5", default="BENCH_PR4.json",
                    help="baseline report bench_pr5 compares against")
    ap.add_argument("--baseline7", default="BENCH_PR5.json",
                    help="baseline report bench_pr7 compares against")
    ap.add_argument("--baseline8", default="BENCH_PR7.json",
                    help="baseline report bench_pr8 compares against")
    ap.add_argument("--bench-out10", default="BENCH_PR10.json",
                    help="path for the bench_pr10 JSON report")
    ap.add_argument("--baseline10", default="BENCH_PR8.json",
                    help="baseline report bench_pr10 compares against")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (broker_bench, fault_bench, fig3_interactions,
                            kernel_bench, lint_bench, prune_bench,
                            roofline_report, shard_bench, speedup_vs_rtree,
                            table2_batching, table3_perfmodel)

    def bench_pr2():
        report = kernel_bench.canonical_report(quick=not args.full)
        with open(args.bench_out, "w") as f:
            json.dump(report, f, indent=2)
        kernel_bench.print_compaction_rows(report["compaction"])
        kernel_bench.print_executor_rows(report["executor"])
        print(f"# bench_pr2 report -> {args.bench_out}")

    def bench_pr3():
        report = kernel_bench.canonical_report_pr3(quick=not args.full)
        with open(args.bench_out3, "w") as f:
            json.dump(report, f, indent=2)
        kernel_bench.print_executor_rows(report["executor"])
        kernel_bench.print_sharded_rows(report["sharded_executor"])
        if os.path.exists(args.baseline):
            with open(args.baseline) as f:
                baseline = json.load(f)
            for line in kernel_bench.compare_executor_sections(report,
                                                               baseline):
                print(line)
        else:
            print(f"# baseline {args.baseline} not found — no comparison")
        print(f"# bench_pr3 report -> {args.bench_out3}")

    def bench_pr4():
        report = broker_bench.canonical_report_pr4(quick=not args.full)
        with open(args.bench_out4, "w") as f:
            json.dump(report, f, indent=2)
        kernel_bench.print_executor_rows(report["executor"])
        broker_bench.print_broker_rows(report["broker"])
        broker_bench.print_broker_sharded_rows(report["broker_sharded"])
        if os.path.exists(args.baseline4):
            with open(args.baseline4) as f:
                baseline = json.load(f)
            for line in kernel_bench.compare_executor_sections(report,
                                                               baseline):
                print(line)
        else:
            print(f"# baseline {args.baseline4} not found — no comparison")
        print(f"# bench_pr4 report -> {args.bench_out4}")

    def bench_pr5():
        report = prune_bench.canonical_report_pr5(quick=not args.full)
        with open(args.bench_out5, "w") as f:
            json.dump(report, f, indent=2)
        kernel_bench.print_executor_rows(report["executor"])
        prune_bench.print_pruning_rows(report["pruning"])
        prune_bench.print_selectivity_rows(report["selectivity"])
        if os.path.exists(args.baseline5):
            with open(args.baseline5) as f:
                baseline = json.load(f)
            for line in kernel_bench.compare_executor_sections(report,
                                                               baseline):
                print(line)
        else:
            print(f"# baseline {args.baseline5} not found — no comparison")
        print(f"# bench_pr5 report -> {args.bench_out5}")

    def bench_pr6():
        report = lint_bench.run(repeats=3 if args.full else 2)
        with open(args.bench_out6, "w") as f:
            json.dump(report, f, indent=2)
        lint_bench.print_rows(report)
        if not report["within_budget"]:
            raise RuntimeError(
                f"lint over the full tree took "
                f"{report['sections']['full_tree']['seconds']:.2f}s — over "
                f"the {lint_bench.BUDGET_SECONDS:.1f}s CI budget")
        print(f"# bench_pr6 report -> {args.bench_out6}")

    def bench_pr7():
        report = prune_bench.canonical_report_pr7(quick=not args.full)
        with open(args.bench_out7, "w") as f:
            json.dump(report, f, indent=2)
        kernel_bench.print_executor_rows(report["executor"])
        prune_bench.print_pruning_mode_rows(report["pruning_modes"])
        if os.path.exists(args.baseline7):
            with open(args.baseline7) as f:
                baseline = json.load(f)
            for line in kernel_bench.compare_executor_sections(report,
                                                               baseline):
                print(line)
        else:
            print(f"# baseline {args.baseline7} not found — no comparison")
        print(f"# bench_pr7 report -> {args.bench_out7}")

    def bench_pr8():
        report = shard_bench.canonical_report_pr8(quick=not args.full)
        with open(args.bench_out8, "w") as f:
            json.dump(report, f, indent=2)
        kernel_bench.print_executor_rows(report["executor"])
        shard_bench.print_shard_sparse_rows(report["shard_sparse"])
        shard_bench.print_cache_rows(report["cache"])
        if os.path.exists(args.baseline8):
            with open(args.baseline8) as f:
                baseline = json.load(f)
            for line in kernel_bench.compare_executor_sections(report,
                                                               baseline):
                print(line)
        else:
            print(f"# baseline {args.baseline8} not found — no comparison")
        print(f"# bench_pr8 report -> {args.bench_out8}")

    def bench_pr10():
        report = fault_bench.canonical_report_pr10(quick=not args.full)
        with open(args.bench_out10, "w") as f:
            json.dump(report, f, indent=2)
        kernel_bench.print_executor_rows(report["executor"])
        fault_bench.print_recovery_rows(report["recovery"])
        if os.path.exists(args.baseline10):
            with open(args.baseline10) as f:
                baseline = json.load(f)
            for line in kernel_bench.compare_executor_sections(report,
                                                               baseline):
                print(line)
        else:
            print(f"# baseline {args.baseline10} not found — no comparison")
        print(f"# bench_pr10 report -> {args.bench_out10}")

    benches = {
        "fig3": lambda: fig3_interactions.main(),
        "table2": lambda: table2_batching.main(),
        "speedup": lambda: speedup_vs_rtree.main(),
        "table3": lambda: table3_perfmodel.main(),
        # classic tile sweep only — compaction/executor live in bench_pr2
        "kernel": lambda: kernel_bench.print_kernel_rows(
            kernel_bench.run(repeats=3 if args.full else 1)),
        "bench_pr2": bench_pr2,
        "bench_pr3": bench_pr3,
        "bench_pr4": bench_pr4,
        "bench_pr5": bench_pr5,
        "bench_pr6": bench_pr6,
        "bench_pr7": bench_pr7,
        "bench_pr8": bench_pr8,
        "bench_pr10": bench_pr10,
        "roofline": lambda: roofline_report.main(),
    }
    only = set(args.only.split(",")) if args.only else None
    failures = 0
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}")
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
