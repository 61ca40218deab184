"""Arithmetic the metric readers in ``bench/metrics`` share.

A reader takes a :class:`bench.harness.Run` and returns its metric's
value, or ``None`` where the run holds nothing to read (another kind of
traffic, or no trace).  It never returns 0 for a share of a roofline.
"""
from __future__ import annotations

import re

import numpy as np

from bench import roofline

#: Device operations of the distance-threshold kernel: the Pallas kernels
#: of ``repro.kernels.distthresh``, as the TPU trace names their custom
#: calls.
KERNEL_OPS = r"distthresh"


def execs(run, kind: str):
    """The window's per-execution records (``"batch"``: one per query
    set) when its traffic is of ``kind``, else None."""
    rec = run.record
    return rec["execs"] if rec.get("kind") == kind else None


def median_ms(values) -> float | None:
    values = list(values)
    return float(np.median(values)) * 1e3 if values else None


def kernel_seconds(run) -> float:
    ops = run.trace.op_seconds if run.trace is not None else {}
    return sum(v for k, v in ops.items() if re.search(KERNEL_OPS, k))


def kernel_roofline(run, kind: str) -> float | None:
    """Percent of the roofline of the kernel's device time in the traced
    window, for the work the window's executions asked for."""
    if execs(run, kind) is None or run.trace is None or run.work is None:
        return None
    ops, nbytes = run.work
    return roofline.roofline_share(ops, nbytes, kernel_seconds(run),
                                   run.device_kind)


def device_idle(run, kind: str) -> float | None:
    """Share of the traced window in which no operation ran on the
    device."""
    if execs(run, kind) is None or run.trace is None:
        return None
    t = run.trace
    if t.window_s <= 0 or t.num_devices == 0:
        return None
    return 1.0 - t.busy_s / t.window_s


def compile_s(run, kind: str) -> float | None:
    """Seconds of tracing, lowering and backend compiling inside the
    window."""
    if execs(run, kind) is None:
        return None
    return float(run.compiles["compile_s"])
