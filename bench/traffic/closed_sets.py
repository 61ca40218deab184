"""Closed loop: one analyst runs query sets back to back.

Each query set is the segments of ``query_set_trajectories`` trajectories
of the database (the configuration's number), through
``db.query(..., backend=<config backend>)``.  The next set starts when
the previous one returned.

Every seed gets the same amount of work: a set takes one trajectory,
drawn from the run seed, from each of ``query_set_trajectories`` strata
of equal work, which the dataset's module forms
(``bench/datasets/<generator>.py``, ``strata``).

Each execution's record copies the program's spans and counters
(``ExecStats.span_seconds`` and ``counts``) under ``spans`` and
``counts``, which the per-layer readers (``bench/spans.py``) read over
every execution of the window.

Parameters (the cell's ``params``):

* ``sets`` — distinct query sets the window cycles through, in order.
  The warm-up runs each of them once, so that the window meets no shape
  that set-up did not (a set's retry capacities follow its hit counts);
* ``check_sets`` — executions checked: a sample of every execution of the
  window, drawn from the seed as the window runs (a reservoir), with every
  query trajectory of each compared;
* ``limits`` — the limit of each compared number.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import datagen, harness, reference
from bench.traffic import common


def prepare(ctx, seconds: float) -> dict:
    p = ctx.cell.params
    n = int(ctx.cell.config["query_set_trajectories"])
    rng = harness.stream(ctx.seed, "window")
    groups = datagen.module(ctx.cell.config).strata(ctx.data, n)
    comps = [np.array([g[rng.integers(len(g))] for g in groups])
             for _ in range(int(p["sets"]))]
    return {"comps": comps,
            "rows": [ctx.data.rows_of(c) for c in comps],
            "segs": [ctx.segments(ctx.data.rows_of(c)) for c in comps],
            "check_rng": harness.stream(ctx.seed, "check"), "n": n}


def _query(ctx, segs):
    return ctx.db.query(segs, ctx.d, backend=ctx.backend)


def warmup(ctx, state) -> None:
    with ctx.span("bench.warmup"):
        for segs in state["segs"]:
            _query(ctx, segs)


def window(ctx, state, seconds: float) -> dict:
    execs, failed = [], 0
    sample = common.Reservoir(int(ctx.cell.params["check_sets"]),
                              state["check_rng"])
    t0 = time.perf_counter()
    i = 0
    while True:
        k = i % len(state["comps"])
        with ctx.span("bench.query_set"):
            ts = time.perf_counter()
            try:
                res = _query(ctx, state["segs"][k])
            except Exception as e:          # a failed set is counted, and
                res = None                  # the analyst sends the next
                failed += 1
                print(f"info set {i} failed: {e!r}", file=sys.stderr)
            wall = time.perf_counter() - ts
        if res is not None:
            st = res.stats
            execs.append({"comp": k, "wall_s": wall,
                          "plan_s": st.plan_seconds,
                          "dispatch_s": st.dispatch_seconds,
                          "sync_s": st.sync_seconds,
                          "qsegs": len(state["rows"][k]), "hits": len(res),
                          "spans": dict(getattr(st, "span_seconds", {})),
                          "counts": dict(getattr(st, "counts", {}))})
            sample.offer((k, res))
            del res
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    return {"kind": "batch", "execs": execs, "elapsed_s": elapsed,
            "attempted": i, "failed": failed, "kept": sample.items}


def check(ctx, state, record) -> tuple[dict, dict]:
    readings = []
    for k, res in record["kept"]:
        lengths = [len(ctx.data.rows_of([t])) for t in state["comps"][k]]
        readings += common.check_result(
            ctx, ctx.columns(state["rows"][k]), lengths, res.query_idx,
            res.entry_traj, res.entry_seg, res.t_enter, res.t_exit)
    extra = {"unchecked": int(not readings),
             "failed_sets": int(record["failed"])}
    return reference.worst(readings), extra


def work(ctx, state, record) -> tuple[float, float]:
    per_set = {}
    ops = nbytes = 0.0
    for e in record["execs"]:
        key = (e["comp"], e["hits"])
        if key not in per_set:
            per_set[key] = common.set_work(ctx, state["rows"][e["comp"]],
                                           e["hits"])
        ops += per_set[key][0]
        nbytes += per_set[key][1]
    return ops, nbytes
