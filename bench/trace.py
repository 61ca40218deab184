"""Reduction of a profiler trace to the numbers the benchmark reports.

A traced run records the measured window with ``jax.profiler`` and puts
host spans (``jax.profiler.TraceAnnotation``) around the calls it makes
into the program.  From the ``.xplane.pb`` this module reads:

* the device's busy time: the union of the intervals in which an operation
  ran on the device, clipped to the window, averaged over the chips used;
* seconds per device operation name, within the window;
* the device's idle time by what the host was doing: each idle gap is cut
  at the edges of the host spans it overlaps (the benchmark's ``bench.``
  spans and the program's ``repro.`` spans), and each piece goes to the
  innermost span that covers it (``"outside"`` where none does).  Only
  the spans of the host thread that runs the window count: a traffic kind
  that queries from that thread (``closed_sets``) has the program's spans
  there; one that hands its queries to other threads sees their time as
  the window thread's own spans or ``"outside"``, and needs a rule of its
  own for which thread keeps the device waiting.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  The patterns are arguments, so a test can
reduce a trace recorded on the CPU, where XLA's operations run on a host
thread.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re

#: Where a TPU trace keeps the device's operations.
TPU_PLANES = r"^/device:TPU:\d+$"
TPU_OP_LINE = r"^XLA Ops$"
#: Prefixes of the host spans: the benchmark's own and the program's.
SPAN_PREFIX = ("bench.", "repro.")
#: The span around the measured window.
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                   # averaged over device planes
    op_seconds: dict                # op name -> seconds (all planes)
    idle_by_span: dict              # host span -> idle seconds, averaged
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


def host_spans(planes: list, prefix=SPAN_PREFIX) -> list:
    """The host events whose name starts with ``prefix`` (a string or a
    tuple of them), by the host thread (the trace's line) that recorded
    them: ``[[(name, start_ns, end_ns)], ...]``, one list per thread."""
    threads = []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            continue
        for _, events in lines:
            spans = [e for e in events if e[0].startswith(prefix)]
            if spans:
                threads.append(spans)
    return threads


def _stretches(spans: list) -> tuple[list, list]:
    """The host timeline cut at every span's edges: the sorted edges, and
    for each stretch between two consecutive edges the innermost (the
    shortest) span that covers it, or ``"outside"``."""
    edges = sorted({x for _, s, e in spans for x in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    active, owners, j = [], [], 0
    for lo in edges[:-1]:
        while j < len(by_start) and by_start[j][1] <= lo:
            name, s, e = by_start[j]
            heapq.heappush(active, (e - s, j, e, name))
            j += 1
        while active and active[0][2] <= lo:
            heapq.heappop(active)
        owners.append(active[0][3] if active else "outside")
    return edges, owners


def _pieces(lo: float, hi: float, edges: list, owners: list):
    """``(owner, ns)`` of each piece of the gap [lo, hi], cut at the
    edges."""
    k = bisect.bisect_right(edges, lo)
    while lo < hi:
        cut = min(hi, edges[k]) if k < len(edges) else hi
        yield (owners[k - 1] if 0 < k <= len(owners) else "outside",
               cut - lo)
        lo, k = cut, k + 1


def reduce(planes: list, *, plane_re: str = TPU_PLANES,
           line_re: str = TPU_OP_LINE, window: str = WINDOW_SPAN
           ) -> Reduced:
    """Busy time, per-op seconds and idle time by host span within the
    ``window`` span, by the spans of the thread that holds it (the whole
    trace and every thread's spans when no such span exists)."""
    threads = host_spans(planes)
    spans = next((t for t in threads if any(s[0] == window for s in t)),
                 [s for t in threads for s in t])
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
    cuts, owners = _stretches([s for s in spans if s[0] != window])
    n = max(len(devices), 1)
    idle: dict = {}
    for s, e in gaps:
        for owner, ns in _pieces(s, e, cuts, owners):
            idle[owner] = idle.get(owner, 0.0) + ns * 1e-9 / n
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
        op_seconds={k: v * 1e-9 for k, v in op_ns.items()},
        idle_by_span=idle, num_devices=len(devices))


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time, and idle time by what the host was doing."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
