"""The reduction from a profiler trace to busy time, per-op seconds and
idle gaps, on a small trace recorded on the CPU (where XLA's operations
run on a host thread) and on planes built by hand."""
import time

import jax
import jax.numpy as jnp
import pytest

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import trace

CPU_PLANES = r"^/host:CPU$"
#: XLA's CPU client runs a program's operations on its own threads and
#: its intra-op pool, whichever the process last set up.
CPU_OP_LINE = r"^tf_XLA"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.query_set"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    return trace.load(trace.profile_file(logdir))


def test_recorded_cpu_trace_reduces(recorded):
    red = trace.reduce(recorded, plane_re=CPU_PLANES, line_re=CPU_OP_LINE)
    assert red.num_devices == 1
    assert 0.06 <= red.window_s < 5.0
    assert 0.0 < red.busy_s < red.window_s
    assert any("dot" in k for k in red.op_seconds)
    assert sum(red.op_seconds.values()) >= red.busy_s * 0.5
    # The sleeps are idle time, and the host was in "bench.wait" then.
    assert red.idle_by_span.get("bench.wait", 0.0) >= 0.05
    assert abs(sum(red.idle_by_span.values())
               - (red.window_s - red.busy_s)) < 1e-6
    out = trace.breakdown(red)
    assert out["idle_gaps"][0][0] == "bench.wait"
    assert len(out["device_ops"]) <= 10


def test_no_device_plane_reads_nothing_busy(recorded):
    red = trace.reduce(recorded)            # the TPU patterns: no match
    assert red.num_devices == 0 and red.busy_s == 0.0
    assert red.op_seconds == {}


def _plane(ops, spans):
    return [("/device:TPU:0", [("XLA Ops", ops), ("Steps", [])]),
            ("/host:CPU", [("python", spans)])]


def test_hand_built_trace():
    ops = [("fusion.1", 10.0, 20.0), ("distthresh_kernel", 15.0, 40.0),
           ("copy", 90.0, 95.0), ("late", 200.0, 210.0)]
    spans = [(trace.WINDOW_SPAN, 0.0, 100.0), ("bench.step", 0.0, 60.0),
             ("bench.submit", 45.0, 50.0), ("bench.wait", 60.0, 100.0)]
    red = trace.reduce(_plane(ops, spans))
    ns = 1e-9
    assert red.window_s == pytest.approx(100 * ns)
    # Busy is the union [10, 40] and [90, 95], clipped to the window.
    assert red.busy_s == pytest.approx(35 * ns)
    assert red.op_seconds == pytest.approx(
        {"fusion.1": 10 * ns, "distthresh_kernel": 25 * ns,
         "copy": 5 * ns})
    # Gap [0, 10] lies in bench.step; [40, 90] is cut at 45, 50 and 60:
    # bench.step, its inner bench.submit, bench.step, bench.wait; [95,
    # 100] lies in bench.wait.
    assert red.idle_by_span == pytest.approx(
        {"bench.step": 25 * ns, "bench.submit": 5 * ns,
         "bench.wait": 35 * ns})


def test_a_gap_is_split_among_the_spans_it_overlaps():
    """One gap, [10, 90], across two nested spans and stretches that no
    span covers: each piece goes to the innermost span over it."""
    ops = [("fusion", 0.0, 10.0), ("copy", 90.0, 100.0)]
    spans = [(trace.WINDOW_SPAN, 0.0, 100.0),
             ("bench.query_set", 20.0, 80.0), ("repro.plan", 30.0, 50.0),
             ("other.span", 0.0, 100.0)]     # neither bench. nor repro.
    red = trace.reduce(_plane(ops, spans))
    ns = 1e-9
    assert red.idle_by_span == pytest.approx(
        {"outside": 20 * ns, "bench.query_set": 40 * ns,
         "repro.plan": 20 * ns})
    assert trace.breakdown(red)["idle_gaps"][0] == [
        "bench.query_set", pytest.approx(40 * ns)]


def test_spans_of_other_threads_own_no_idle_time():
    """Idle time goes to the spans of the thread that holds the window; a
    span that another thread records over the same gap owns none of it,
    unless the trace has no window span."""
    ops = [("fusion", 0.0, 10.0), ("copy", 90.0, 100.0)]
    main = [("bench.query_set", 0.0, 100.0)]
    worker = [("repro.plan", 20.0, 60.0)]
    ns = 1e-9

    def planes(main):
        return [("/device:TPU:0", [("XLA Ops", ops)]),
                ("/host:CPU", [("python", main), ("worker", worker)])]

    red = trace.reduce(planes([(trace.WINDOW_SPAN, 0.0, 100.0)] + main))
    assert red.idle_by_span == pytest.approx({"bench.query_set": 80 * ns})
    red = trace.reduce(planes(main))
    assert red.idle_by_span == pytest.approx(
        {"bench.query_set": 40 * ns, "repro.plan": 40 * ns})


def test_op_name_drops_the_hlo_text():
    event = ("%distthresh_compact_pallas.1 = (s32[34,128]{1,0:T(8,128)}, "
             "f32[1,1]{1,0}) custom-call(f32[1,1]{1,0} %bitcast.14), "
             "custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(event) == "%distthresh_compact_pallas.1"
    assert trace.op_name("wrapped_sine") == "wrapped_sine"
