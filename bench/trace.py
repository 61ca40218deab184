"""Reduction of a profiler trace to the numbers the benchmark reports.

A traced run records the measured window with ``jax.profiler`` and puts
host spans (``jax.profiler.TraceAnnotation``) around the calls it makes
into the program.  From the ``.xplane.pb`` this module reads:

* the device's busy time: the union of the intervals in which an operation
  ran on the device, clipped to the window, averaged over the chips used;
* seconds per device operation name, within the window;
* the device's idle gaps, each given to the innermost benchmark host span
  that covers its midpoint (``"outside"`` when none does).

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  The patterns are arguments, so a test can
reduce a trace recorded on the CPU, where XLA's operations run on a host
thread.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: Where a TPU trace keeps the device's operations.
TPU_PLANES = r"^/device:TPU:\d+$"
TPU_OP_LINE = r"^XLA Ops$"
#: Prefix of the benchmark's own host spans.
SPAN_PREFIX = "bench."
#: The span around the measured window.
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                   # averaged over device planes
    op_seconds: dict                # op name -> seconds (all planes)
    idle_by_span: dict              # host span -> idle seconds, averaged
    longest_gaps: list              # [(span, seconds)] longest first
    num_devices: int


def profile_file(logdir: str) -> str:
    """The newest ``.xplane.pb`` under ``logdir``."""
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(files, key=os.path.getmtime)


def op_name(event: str) -> str:
    """A device operation's name without its HLO text: the TPU trace
    names an ``XLA Ops`` event ``%<name> = <shape> <op>(<operands>)``."""
    return event.split(" = ", 1)[0]


def load(path: str) -> list:
    """Planes as ``[(plane name, [(line name, [(event name, start_ns,
    end_ns)])])]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(op_name(e.name), float(e.start_ns),
                                       float(e.start_ns + e.duration_ns))
                                      for e in line.events]))
        out.append((plane.name, lines))
    return out


def _union(intervals: list, lo: float, hi: float) -> list:
    """Merged intervals clipped to [lo, hi], in order."""
    merged = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def host_spans(planes: list, prefix: str = SPAN_PREFIX) -> list:
    """Every host event whose name starts with ``prefix``:
    ``[(name, start_ns, end_ns)]``."""
    spans = []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            continue
        for _, events in lines:
            spans.extend(e for e in events if e[0].startswith(prefix))
    return spans


def reduce(planes: list, *, plane_re: str = TPU_PLANES,
           line_re: str = TPU_OP_LINE, window: str = WINDOW_SPAN,
           top: int = 10) -> Reduced:
    """Busy time, per-op seconds and idle gaps within the ``window`` span
    (the whole trace when no such span exists)."""
    spans = host_spans(planes)
    devices = []
    for pname, lines in planes:
        if not re.search(plane_re, pname):
            continue
        ops = [e for lname, events in lines if re.search(line_re, lname)
               for e in events]
        if ops:
            devices.append(ops)
    win = [s for s in spans if s[0] == window]
    if win:
        lo, hi = min(s[1] for s in win), max(s[2] for s in win)
    else:
        every = [e for ops in devices for e in ops] + spans
        lo = min((e[1] for e in every), default=0.0)
        hi = max((e[2] for e in every), default=0.0)
    op_ns: dict = {}
    busy = 0.0
    gaps = []
    for ops in devices:
        for name, s, e in ops:
            overlap = min(e, hi) - max(s, lo)
            if overlap > 0:
                op_ns[name] = op_ns.get(name, 0.0) + overlap
        merged = _union([(s, e) for _, s, e in ops], lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    inner = sorted((s for s in spans if s[0] != window),
                   key=lambda s: s[2] - s[1])
    n = max(len(devices), 1)
    idle: dict = {}
    named = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        owner = next((sp[0] for sp in inner if sp[1] <= mid <= sp[2]),
                     "outside")
        idle[owner] = idle.get(owner, 0.0) + (e - s) * 1e-9 / n
        named.append((owner, (e - s) * 1e-9))
    named.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
        op_seconds={k: v * 1e-9 for k, v in op_ns.items()},
        idle_by_span=idle, longest_gaps=named[:top],
        num_devices=len(devices))


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time, and idle time by what the host was doing."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
