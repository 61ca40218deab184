"""One run of one cell: set-up, warm-up, the measured window, the check.

``run`` drives a cell's traffic driver (``bench/traffic/<kind>.py``)
through four calls, in order:

* ``prepare(ctx)`` — draw the cell's work from the seed;
* ``warmup(ctx, state)`` — meet every shape the window will use;
* ``window(ctx, state, seconds)`` — the measured window, returning a
  record of what it did;
* ``check(ctx, state, record)`` — compare what the window produced with
  the plain reference (``bench.reference``).

Set-up (``setup_s``) runs from the start of the data generation to the
end of the warm-up.  The result line holds the cell's end-to-end metrics
(``--trace 0``) or its per-layer metrics (``--trace 1``), each read by its
reader in ``bench/metrics``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import cells, datagen, reference, trace as tracemod

#: The persistent compilation cache, inside the checkout at a fixed path.
CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")

#: Streams drawn from a run's seed, one per use.
STREAMS = {"window": 2, "check": 3}


def enable_compile_cache(path: str = CACHE_DIR) -> str:
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileMonitor:
    """Counts what JAX compiles: lowerings (a new shape met in this
    process), backend compiles, persistent-cache hits, and the seconds of
    tracing, lowering and backend compiling together (``compile_s``).  A
    shape met anew costs its tracing and lowering in every process, even
    when the persistent cache spares the backend compile.  One listener
    per process; ``snapshot`` and differences of snapshots give the
    counts over a stretch of the run."""

    _instance = None

    #: Duration events whose seconds count in ``compile_s``.
    SECONDS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.counts = {"lowerings": 0, "compiles": 0, "compile_s": 0.0,
                       "cache_hits": 0}

    @classmethod
    def get(cls) -> "CompileMonitor":
        if cls._instance is None:
            import jax
            mon = cls()

            def on_duration(event: str, seconds: float, **_):
                if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                    mon.counts["lowerings"] += 1
                elif event == "/jax/core/compile/backend_compile_duration":
                    mon.counts["compiles"] += 1
                if event in cls.SECONDS:
                    mon.counts["compile_s"] += seconds

            def on_event(event: str, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    mon.counts["cache_hits"] += 1

            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
            cls._instance = mon
        return cls._instance

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, snap: dict) -> dict:
        return {k: self.counts[k] - snap[k] for k in self.counts}


def stream(seed: int, name: str) -> np.random.Generator:
    """An independent random stream of ``seed`` for one use."""
    return np.random.default_rng(
        np.random.SeedSequence(int(seed), spawn_key=(STREAMS[name],)))


@dataclasses.dataclass
class Context:
    """What a traffic driver works with."""

    cell: cells.Cell
    seed: int
    data: datagen.Dataset
    db: object                      # repro.api.TrajectoryDB
    d: float
    backend: str
    tracing: bool
    monitor: CompileMonitor
    #: when a list, every check also compares the control (the reference
    #: in bfloat16) on the same queries and appends its numbers here
    control: list | None = None

    @functools.cached_property
    def index(self) -> reference.EntryIndex:
        """The reference's own sorted entries, built at first use: after
        the window, so that set-up does not pay for the reference."""
        return reference.EntryIndex(self.data)

    def span(self, name: str):
        """A host span in the traced run; nothing otherwise."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def segments(self, rows: np.ndarray):
        """The program's segment array for dataset rows ``rows``."""
        from repro.core.segments import SegmentArray
        return SegmentArray(**{c: self.data.cols[c][rows]
                               for c in datagen.COLUMNS},
                            seg_id=self.data.seg_id[rows],
                            traj_id=self.data.traj_id[rows])

    def columns(self, rows: np.ndarray) -> dict:
        """The reference's query columns for dataset rows ``rows``."""
        return {c: self.data.cols[c][rows] for c in datagen.COLUMNS}


def build(cell: cells.Cell, seed: int, *, tracing: bool = False
          ) -> Context:
    """The cell's database, built through the program's facade from data
    drawn from ``seed``."""
    from repro.api import ExecutionPolicy, TrajectoryDB
    cfg = cell.config
    data = datagen.make(cfg, seed)
    policy = ExecutionPolicy(**cfg.get("policy", {}))
    ctx = Context(cell=cell, seed=seed, data=data, db=None,
                  d=float(cfg["d"]),
                  backend=cfg["backend"], tracing=tracing,
                  monitor=CompileMonitor.get())
    ctx.db = TrajectoryDB.from_segments(ctx.segments(np.arange(len(data))),
                                        policy=policy)
    return ctx


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: cells.Cell
    setup_s: float
    record: dict                    # the traffic driver's window record
    compiles: dict                  # CompileMonitor counts in the window
    device_kind: str
    trace: tracemod.Reduced | None = None
    work: tuple | None = None       # (ops, bytes) of the window's work


def peak_memory(devices) -> int:
    peaks = [int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for dev in devices]
    return max(peaks, default=-1)


def judge(readings: dict, limits: dict) -> bool:
    return all(readings[k] <= limits[k] for k in reference.NUMBERS)


def report_checks(readings: dict, limits: dict, extra: dict) -> dict:
    """The compared numbers beside their limits, as the result line's
    last key, and as the last lines of standard error."""
    out = {k: {"value": readings[k], "limit": limits[k]}
           for k in reference.NUMBERS}
    out.update({k: {"value": v, "limit": 0} for k, v in extra.items()})
    for k, v in out.items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return out


def run(cell: cells.Cell, seed: int, seconds: float, tracing: bool,
        devices) -> dict:
    """One run of ``cell``; returns the result line's object."""
    drv = cells.traffic(cell.traffic)
    monitor = CompileMonitor.get()
    t0 = time.perf_counter()
    ctx = build(cell, seed, tracing=tracing)
    state = drv.prepare(ctx, seconds)
    drv.warmup(ctx, state)
    setup_s = time.perf_counter() - t0
    print(f"info setup_s={setup_s:.3f} warm-up {monitor.snapshot()}",
          file=sys.stderr, flush=True)

    snap = monitor.snapshot()
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if tracing else None
    if tracing:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with ctx.span(tracemod.WINDOW_SPAN):
            record = drv.window(ctx, state, seconds)
    finally:
        if tracing:
            import jax
            jax.profiler.stop_trace()
    compiles = monitor.since(snap)
    memory = peak_memory(devices[:cell.chips])
    kind = devices[0].device_kind
    result_run = Run(cell=cell, setup_s=setup_s, record=record,
                     compiles=compiles, device_kind=kind)
    reduced = None
    if tracing:
        try:
            reduced = tracemod.reduce(tracemod.load(
                tracemod.profile_file(logdir)))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        result_run.trace = reduced
        result_run.work = drv.work(ctx, state, record)
    if compiles["lowerings"] or compiles["compiles"]:
        print(f"info compiled inside the window: {compiles}",
              file=sys.stderr, flush=True)

    # The reference runs after the window and after the memory reading.
    t_check = time.perf_counter()
    readings, extra = drv.check(ctx, state, record)
    print(f"info check_s={time.perf_counter() - t_check:.3f}",
          file=sys.stderr, flush=True)
    limits = cell.params["limits"]
    correct = judge(readings, limits) and not any(extra.values())

    section = "per_layer" if tracing else "end_to_end"
    metrics = {}
    for m in cells.metrics_for(cell.name, section):
        value = cells.reader(m["name"])(result_run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    out = {"correct": bool(correct), "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        out["breakdown"] = tracemod.breakdown(reduced)
    out["checks"] = report_checks(readings, limits, extra)
    return out
