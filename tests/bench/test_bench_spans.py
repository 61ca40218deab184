"""The readers of the program's own spans and counters (``bench.spans``):
their values on hand-built execution records, nothing on records of a
program that records no spans, their values on a real window on the CPU,
and the program's spans in a recorded trace where the benchmark reads
host spans."""
import types

import jax
import pytest

from _bench_tiny import tiny_cell
from bench import cells, harness, trace

NEW = ("dispatch_ms.batch", "h2d_mb.batch", "retry_ms.batch",
       "retry_share.batch", "fetch_ms.batch", "assemble_ms.batch",
       "canonical_ms.batch", "slot_use.batch")


def _stats(scale):
    span_seconds = {"repro.engine.dispatch": 0.010 * scale,
                    "repro.exec.retry": 0.020 * scale,
                    "repro.exec.marshal": 0.050 * scale,
                    "repro.engine.fetch": 0.030 * scale,
                    "repro.exec.concat": 0.004 * scale,
                    "repro.facade.canonical": 0.100 * scale}
    counts = {"dispatches": 20, "retried_dispatches": 5 * scale,
              "h2d_bytes": 2_000_000 * scale, "result_slots": 1000,
              "result_rows": 250 * scale}
    return types.SimpleNamespace(span_seconds=span_seconds, counts=counts)


def _exec(stats):
    """An execution's record, with the spans and counters of ``stats``
    where it has them, as ``closed_sets`` copies them."""
    rec = {"comp": 0, "wall_s": 1.0, "plan_s": 0.25, "dispatch_s": 0.25,
           "sync_s": 0.25, "qsegs": 10, "hits": 5}
    if hasattr(stats, "span_seconds"):
        rec.update(spans=dict(stats.span_seconds), counts=dict(stats.counts))
    return rec


def _run(kind="batch", stats=(1, 2, 3), kept=(5, 6)):
    stats = [_stats(s) if isinstance(s, int) else s for s in stats]
    rec = {"kind": kind, "elapsed_s": 2.0, "attempted": len(stats),
           "failed": 0, "execs": [_exec(st) for st in stats],
           "kept": [(k, types.SimpleNamespace(stats=_stats(s)))
                    for k, s in enumerate(kept)]}
    return harness.Run(cell=None, setup_s=3.0, record=rec,
                       compiles={"compile_s": 0.0},
                       device_kind="TPU v5 lite")


#: The median (scale 2) of the three hand-built executions; the two kept
#: ones read scale 5.5.
WANT = {"dispatch_ms.batch": 20.0, "h2d_mb.batch": 4.0,
        "retry_ms.batch": 40.0, "retry_share.batch": 0.5,
        "fetch_ms.batch": 60.0, "assemble_ms.batch": 48.0,
        "canonical_ms.batch": 200.0, "slot_use.batch": 0.5}


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_the_median_of_the_kept_executions(name):
    """The median over every execution record of the window, not over
    the executions kept for the check."""
    assert cells.reader(name)(_run()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_without_the_program_spans(name):
    read = cells.reader(name)
    older = types.SimpleNamespace(plan_seconds=0.1, total_seconds=1.0)
    assert read(_run(stats=(older, older))) is None
    assert read(_run(stats=())) is None
    assert read(_run(kind="other")) is None


def test_a_set_without_retries_reads_zero():
    st = _stats(1)
    del st.span_seconds["repro.exec.retry"], st.counts["retried_dispatches"]
    run = _run(stats=(st,))
    assert cells.reader("retry_ms.batch")(run) == 0.0
    assert cells.reader("retry_share.batch")(run) == 0.0


@pytest.fixture(scope="module")
def window():
    """A window of the tiny cell on the CPU, driven past the harness's
    look for a TPU, as ``harness.run`` drives it."""
    cell = tiny_cell("s2.batch")
    drv = cells.traffic(cell.traffic)
    ctx = harness.build(cell, 2 ** 33 + 7)
    state = drv.prepare(ctx, 0.5)
    drv.warmup(ctx, state)
    record = drv.window(ctx, state, 0.5)
    return harness.Run(cell=cell, setup_s=1.0, record=record,
                       compiles={"compile_s": 0.0}, device_kind="cpu")


def test_readers_read_the_program(window):
    got = {name: cells.reader(name)(window) for name in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert 0 < got["slot_use.batch"] <= 1
    assert 0 <= got["retry_share.batch"] <= 1
    assert got["h2d_mb.batch"] > 0 and got["dispatch_ms.batch"] > 0
    # Every execution of the window carries its spans and counters.
    for e in window.record["execs"]:
        assert e["counts"]["result_rows"] == e["hits"]
        assert e["spans"]["repro.query"] <= e["wall_s"]
    for _, res in window.record["kept"]:
        st = res.stats
        assert st.counts["result_rows"] == len(res)
        # marshal_ms.batch's remainder holds these steps and no plan,
        # dispatch or sync seconds.
        parts = sum(st.span_seconds.get(n, 0.0) for n in (
            "repro.exec.retry", "repro.exec.marshal", "repro.exec.concat",
            "repro.facade.canonical"))
        assert parts <= st.span_seconds["repro.query"] - (
            st.plan_seconds + st.dispatch_seconds + st.sync_seconds)


def test_program_spans_reach_the_host_plane(tmp_path):
    """The program's spans land where the benchmark reads host spans: on
    the thread that holds the window, nested in the benchmark's span
    around ``db.query``."""
    cell = tiny_cell("s2.batch")
    ctx = harness.build(cell, 2 ** 31 + 3)
    segs = ctx.segments(ctx.data.rows_of([0, 1]))
    ctx.db.query(segs, ctx.d, backend=ctx.backend)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.query_set"):
                ctx.db.query(segs, ctx.d, backend=ctx.backend)
    finally:
        jax.profiler.stop_trace()
    planes = trace.load(trace.profile_file(str(tmp_path)))
    found = next(t for t in trace.host_spans(planes)
                 if any(s[0] == trace.WINDOW_SPAN for s in t))
    outer = next(s for s in found if s[0] == "bench.query_set")
    inner = {s[0] for s in found
             if s[0].startswith("repro.") and outer[1] <= s[1]
             and s[2] <= outer[2]}
    assert {"repro.query", "repro.plan", "repro.engine.dispatch",
            "repro.exec.sync", "repro.exec.marshal",
            "repro.facade.canonical"} <= inner
