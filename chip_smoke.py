"""Smoke run of the distance-threshold query path, compiled, on TPU chips.

Drives the public facade once at the paper's data size and checks what
comes out:

* **S2** — the paper's Galaxy scenario (about 10^6 database segments, 100
  query trajectories, d = 5) through ``db.query(backend="pallas")`` with the
  default policy (spatial pruning, fused in-kernel compaction).  Rows must
  equal ``backend="jnp"``'s exactly in ``entry_idx`` / ``query_idx`` and
  agree in ``t_enter`` / ``t_exit`` to the tolerance the kernel tests use;
  the rows of a few query trajectories must also match ``backend="brute"``.
* **C3** — the twin-swarm scenario with the hierarchical index, so the
  live-tile kernel runs; checked against ``backend="jnp"`` the same way.
* **broker** — ``db.broker(backend="pallas")`` takes the S2 queries as four
  tickets; their slices, concatenated, must be byte-identical to the S2
  ``db.query`` rows, each group with at most two host syncs, no ticket
  degraded.

Each phase also proves that the compiled kernel ran: the lowered text of a
step the engine dispatched must hold a ``tpu_custom_call``.

With ``--chips 4`` it runs only the mesh phase: S2 and C3 through
``backend="shard"`` on four temporal pods with the compiled kernel, checked
against the single-device ``backend="pallas"`` rows on the first chip.

Lines before the last are smoke information (set-up, compile and wall
seconds, interactions, hits, peak device bytes), not benchmark metrics.
The last line is one JSON object: ``{"ok": true, "device": {...}}``.  The
script exits non-zero, before any work, when JAX finds no TPU, and on any
mismatch or error.

Run from the root of a checkout:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-pod mesh phase
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
FIELDS = ("entry_idx", "entry_traj", "entry_seg", "query_idx", "t_enter",
          "t_exit")
INDEX_FIELDS = ("entry_idx", "entry_traj", "entry_seg", "query_idx")
#: Interval agreement across kernels (tests/test_kernels.py's tolerance).
RTOL, ATOL = 1e-4, 1e-3


def info(phase: str, **fields) -> None:
    """One line of smoke information (not a metric)."""
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"smoke-info [{phase}] {body}", flush=True)


def check_rows(got, want, label: str, *, exact_times: bool) -> None:
    """Row-for-row agreement of two canonical results."""
    import numpy as np
    if len(got) != len(want):
        raise AssertionError(f"{label}: {len(got)} rows, expected "
                             f"{len(want)}")
    for f in INDEX_FIELDS:
        if not np.array_equal(getattr(got, f), getattr(want, f)):
            raise AssertionError(f"{label}: {f} differs")
    for f in ("t_enter", "t_exit"):
        a, b = getattr(got, f), getattr(want, f)
        if exact_times:
            if not np.array_equal(a, b):
                raise AssertionError(f"{label}: {f} not byte-identical")
        elif not np.allclose(a, b, rtol=RTOL, atol=ATOL):
            bad = np.flatnonzero(~np.isclose(a, b, rtol=RTOL, atol=ATOL))
            k = bad[np.argmax(np.abs(a[bad] - b[bad]))]
            raise AssertionError(
                f"{label}: {f} differs in {len(bad)} rows; worst row {k} "
                f"(entry {got.entry_idx[k]}, query {got.query_idx[k]}): "
                f"{a[k]!r} vs {b[k]!r}; zeros {int(np.sum(a[bad] == 0))} "
                f"vs {int(np.sum(b[bad] == 0))}")


class DispatchLog:
    """Records the device steps the engines dispatch, so the smoke can
    lower one of them and see what ran.

    Wraps ``ops._query_block_jit`` (the single-device step) and the pod
    dispatcher's launch (the mesh step) for the duration of a ``with``.
    """

    def __init__(self):
        self.single: list[tuple[tuple, dict]] = []
        self.mesh: list[tuple[object, int, tuple]] = []

    def __enter__(self):
        from repro.core import distributed
        from repro.kernels import ops
        self._ops, self._dist = ops, distributed
        self._step = ops._query_block_jit
        self._launch = distributed._PodShardDispatcher._launch
        log = self

        def step(*args, **kwargs):
            if len(log.single) < 64:
                log.single.append((args, kwargs))
            return log._step(*args, **kwargs)

        def launch(disp, batch, capacity, prepared):
            if len(log.mesh) < 8:
                log.mesh.append((disp.engine, capacity, prepared))
            return log._launch(disp, batch, capacity, prepared)

        ops._query_block_jit = step
        distributed._PodShardDispatcher._launch = launch
        return self

    def __exit__(self, *exc):
        self._ops._query_block_jit = self._step
        self._dist._PodShardDispatcher._launch = self._launch
        return False

    def single_text(self, pick) -> str:
        """Lowered text of the first recorded single-device step whose
        keyword arguments satisfy ``pick``."""
        for args, kwargs in self.single:
            if pick(kwargs):
                return self._step.lower(*args, **kwargs).as_text()
        raise AssertionError("no dispatched step matched")

    def mesh_text(self) -> str:
        """Lowered text of the first recorded mesh step."""
        import jax.numpy as jnp
        import numpy as np
        engine, capacity, (stacked, offsets, lens, qs) = self.mesh[0]
        d = np.float32(1.0)
        args = ((stacked, offsets, lens, qs, d) if engine.sparse
                else (stacked, offsets, qs, d))
        return engine._fn(capacity).lower(
            *(jnp.asarray(a) for a in args)).as_text()


def assert_compiled(text: str, label: str) -> None:
    """The lowered step holds a compiled Pallas kernel."""
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{label}: the dispatched step holds no "
                             f"compiled Pallas kernel")


def assert_engine_compiled(eng, label: str) -> None:
    """The engine runs the compiled Pallas kernel, not the interpreter or
    the jnp oracle."""
    if not eng.use_pallas or eng.interpret:
        raise AssertionError(f"{label}: use_pallas={eng.use_pallas}, "
                             f"interpret={eng.interpret}")


def assert_dispatches_compiled(log: DispatchLog, label: str) -> None:
    """Every recorded single-device step ran the compiled kernel."""
    if not log.single:
        raise AssertionError(f"{label}: nothing was dispatched")
    for _, kw in log.single:
        if kw["interpret"] or not kw["use_pallas"]:
            raise AssertionError(f"{label}: a step ran interpreted or jnp")


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def subset_of_trajectories(queries, k: int):
    """Caller positions of the segments of the first ``k`` query
    trajectories."""
    import numpy as np
    tids = np.unique(queries.traj_id)[:k]
    return np.flatnonzero(np.isin(queries.traj_id, tids))


def rows_for(result, idx):
    """``result``'s rows whose query is at one of the caller positions
    ``idx`` (sorted), re-indexed to positions within ``idx``."""
    import numpy as np
    from repro.api import QueryResult
    keep = np.isin(result.query_idx, idx)
    return QueryResult(
        result.entry_idx[keep], result.entry_traj[keep],
        result.entry_seg[keep],
        np.searchsorted(idx, result.query_idx[keep]),
        result.t_enter[keep], result.t_exit[keep], d=result.d,
        backend=result.backend)


def phase_s2(scale: float, seed: int, brute_trajectories: int = 3):
    """S2 through ``backend="pallas"``, checked against jnp and brute.
    Returns the database and the pallas result (the broker phase reuses
    them)."""
    from repro.api import ExecutionPolicy, TrajectoryDB
    db, setup = timed(lambda: TrajectoryDB.from_scenario(
        "S2", scale=scale, seed=seed))
    queries, d = db.scenario_queries, db.scenario_d
    info("S2", setup_s=f"{setup:.3f}", db_segments=len(db),
         query_segments=len(queries), d=d)
    with DispatchLog() as log:
        res, wall = timed(lambda: db.query(queries, d, backend="pallas"))
    eng = db.engine("pallas")
    assert_engine_compiled(eng, "S2")
    if eng.compaction != "fused" or eng.pruning != "spatial":
        raise AssertionError(f"S2: policy {eng.compaction}/{eng.pruning}")
    assert_dispatches_compiled(log, "S2")
    assert_compiled(log.single_text(lambda kw: True), "S2")
    if len(res) == 0:
        raise AssertionError("S2: no hits")
    st = res.stats
    info("S2", backend="pallas", wall_s=f"{wall:.3f}",
         interactions=res.plan.total_interactions, hits=len(res),
         batches=res.plan.num_batches, num_syncs=st.num_syncs,
         pruned_tiles=st.pruned_tiles, tiles=st.total_tiles,
         peak_bytes=peak_bytes())

    ref, wall = timed(lambda: db.query(queries, d, backend="jnp"))
    info("S2", backend="jnp", wall_s=f"{wall:.3f}", hits=len(ref))
    check_rows(res, ref, "S2 pallas vs jnp", exact_times=False)

    idx = subset_of_trajectories(queries, brute_trajectories)
    pol = ExecutionPolicy(brute_chunk=8192)
    brute, wall = timed(lambda: db.query(queries.take(idx), d,
                                         backend="brute", policy=pol))
    info("S2", backend="brute", wall_s=f"{wall:.3f}",
         query_trajectories=brute_trajectories, query_segments=len(idx),
         hits=len(brute))
    check_rows(rows_for(res, idx), brute, "S2 pallas vs brute",
               exact_times=False)
    return db, res


def phase_c3(scale: float, seed: int):
    """C3 with the hierarchical index: the live-tile kernel."""
    from repro.api import ExecutionPolicy, TrajectoryDB
    pol = ExecutionPolicy(pruning="hierarchical", index_kboxes=4,
                          num_bins=8)
    db, setup = timed(lambda: TrajectoryDB.from_scenario(
        "C3", scale=scale, seed=seed, policy=pol))
    queries, d = db.scenario_queries, db.scenario_d
    info("C3", setup_s=f"{setup:.3f}", db_segments=len(db),
         query_segments=len(queries), d=d)
    with DispatchLog() as log:
        res, wall = timed(lambda: db.query(queries, d, backend="pallas"))
    assert_dispatches_compiled(log, "C3")
    assert_compiled(log.single_text(lambda kw: kw.get("tile_i") is not None),
                    "C3 live-tile")
    st = res.stats
    info("C3", backend="pallas", wall_s=f"{wall:.3f}",
         interactions=res.plan.total_interactions, hits=len(res),
         batches=res.plan.num_batches, num_syncs=st.num_syncs,
         pruned_tiles=st.pruned_tiles, tiles=st.total_tiles,
         peak_bytes=peak_bytes())
    ref, wall = timed(lambda: db.query(queries, d, backend="jnp"))
    info("C3", backend="jnp", wall_s=f"{wall:.3f}", hits=len(ref))
    if len(ref) == 0:
        raise AssertionError("C3: no hits")
    check_rows(res, ref, "C3 pallas vs jnp", exact_times=False)


def phase_broker(db, base, tickets: int = 4):
    """The S2 queries as ``tickets`` broker tickets; their slices must
    concatenate to ``base`` (the ``db.query`` rows) byte for byte."""
    import numpy as np
    from repro.api import QueryResult
    queries, d = db.scenario_queries, db.scenario_d
    bounds = np.linspace(0, len(queries), tickets + 1).astype(int)
    t0 = time.perf_counter()
    with DispatchLog() as log:
        broker = db.broker(backend="pallas")
        submitted = [(lo, broker.submit(queries.take(np.arange(lo, hi)), d))
                     for lo, hi in zip(bounds[:-1], bounds[1:])]
        broker.run_until_idle()
    wall = time.perf_counter() - t0
    assert_dispatches_compiled(log, "broker")
    assert_compiled(log.single_text(lambda kw: True), "broker")
    parts = []
    for lo, ticket in submitted:
        if ticket.exception() is not None:
            raise AssertionError(f"broker: ticket failed: "
                                 f"{ticket.exception()!r}")
        if ticket.health.degraded:
            raise AssertionError(f"broker: ticket degraded: "
                                 f"{ticket.health.degradations}")
        syncs = [sl.num_syncs for sl in ticket.slices()]
        if max(syncs) > 2:
            raise AssertionError(f"broker: {max(syncs)} syncs in a group")
        for sl in ticket.slices():
            r = sl.result
            parts.append(QueryResult(
                r.entry_idx, r.entry_traj, r.entry_seg, r.query_idx + lo,
                r.t_enter, r.t_exit, d=d, backend="pallas"))
    concat = QueryResult(*(np.concatenate([getattr(p, f) for p in parts])
                           for f in FIELDS), d=d, backend="pallas")
    check_rows(concat, base, "broker slices vs db.query", exact_times=True)
    info("broker", tickets=tickets,
         groups=sum(t.num_groups for _, t in submitted),
         wall_s=f"{wall:.3f}", hits=len(concat), peak_bytes=peak_bytes())


def phase_mesh(scale: float, seed: int, pods: int = 4):
    """S2 and C3 through ``backend="shard"`` on ``pods`` chips, checked
    against the single-device pallas rows."""
    from repro.api import ExecutionPolicy, TrajectoryDB
    cases = (("S2", ExecutionPolicy(shard_pods=pods)),
             ("C3", ExecutionPolicy(shard_pods=pods, pruning="hierarchical",
                                    index_kboxes=4, num_bins=8)))
    for name, pol in cases:
        db, setup = timed(lambda: TrajectoryDB.from_scenario(
            name, scale=scale, seed=seed, policy=pol))
        queries, d = db.scenario_queries, db.scenario_d
        info(f"mesh {name}", setup_s=f"{setup:.3f}", db_segments=len(db),
             query_segments=len(queries), pods=pods)
        single, wall = timed(lambda: db.query(queries, d, backend="pallas"))
        info(f"mesh {name}", backend="pallas", wall_s=f"{wall:.3f}",
             hits=len(single))
        with DispatchLog() as log:
            res, wall = timed(lambda: db.query(queries, d, backend="shard"))
        eng = db.backend("shard").engine
        if eng.ways != pods:
            raise AssertionError(f"mesh {name}: {eng.ways} pods")
        assert_engine_compiled(eng, f"mesh {name}")
        assert_compiled(log.mesh_text(), f"mesh {name}")
        info(f"mesh {name}", backend="shard", wall_s=f"{wall:.3f}",
             hits=len(res), num_syncs=res.stats.num_syncs,
             peak_bytes=peak_bytes())
        if len(single) == 0:
            raise AssertionError(f"mesh {name}: no hits")
        check_rows(res, single, f"mesh {name} shard vs pallas",
                   exact_times=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-pod mesh phase")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="scenario scale (1.0 = the paper's size)")
    ap.add_argument("--seed", type=int, default=0, help="data seed")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: run from a checkout of the repository "
              "(src/repro not found)", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    compile_s = [0.0]

    def on_duration(event: str, seconds: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            compile_s[0] += seconds

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    info("device", platform=dev.platform, kind=repr(dev.device_kind),
         count=len(devices), scale=args.scale, seed=args.seed,
         compile_cache=cache)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(args.scale, args.seed)
    else:
        db, res = phase_s2(args.scale, args.seed)
        phase_c3(args.scale, args.seed)
        phase_broker(db, res)
    info("total", wall_s=f"{time.perf_counter() - t0:.3f}",
         compile_s=f"{compile_s[0]:.3f}", peak_bytes=peak_bytes())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
