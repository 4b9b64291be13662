"""The trace reducer: a profiler trace (``.xplane.pb``) made into device
busy time, kernel and merge time, the top device operations, and the
longest idle gaps named by what the harness's host threads were doing.

Device planes are those named ``/device:TPU:<n>``.  On each, the
``XLA Ops`` line holds one event per executed operation and the
``XLA Modules`` line one per executed program.  Busy time is the union
of the operation intervals inside the traced window; the window is the
harness's ``bench.window`` host span.

The fused fold kernel is found by its operation's name
(``%fused_fold_pallas.<n> = ... custom-call``, the jitted wrapper of the
Pallas call), and the merge by its program's name (``jit_mf``, the
engine's funnel merge+finalize).  Both names come from the program and
are the only things this module takes from it.

An idle gap is named by what the harness was doing: where no query was
in flight for most of it, ``bench.generator.sleep`` (waiting for the next
arrival); else the harness span that overlaps it most (``bench.fetch``,
``bench.submit``); else ``program`` (the system's own host code).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

KERNEL_RE = re.compile(r"^%fused_fold_pallas(\.\d+)? = .*custom-call")
MERGE_RE = re.compile(r"^jit_mf\(")
DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
#: host spans of the harness that name an idle gap while a query is in
#: flight (it also records ``bench.generator.sleep``, the name of a gap
#: with none in flight)
HOST_SPANS = ("bench.submit", "bench.fetch")
WINDOW_SPAN = "bench.window"

Interval = Tuple[int, int]


def find_xplane(root: str) -> str:
    """The newest ``.xplane.pb`` under ``root`` (or ``root`` itself)."""
    if os.path.isfile(root):
        return root
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def reduce_trace(path: str,
                 inflight: Sequence[Tuple[float, float]] = ()) -> Dict:
    """Reduce one trace file; times in seconds.  ``inflight`` holds each
    query's (submitted, answered) times in seconds after the window
    opened, for naming idle gaps."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    planes = list(pd.planes)
    host: Dict[str, List[Interval]] = {n: [] for n in HOST_SPANS}
    window: Optional[Interval] = None
    for plane in planes:
        if DEVICE_PLANE_RE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in host:
                    host[ev.name].append((int(ev.start_ns), int(ev.end_ns)))
                elif ev.name == WINDOW_SPAN:
                    window = (int(ev.start_ns), int(ev.end_ns))
    devices = [p for p in planes if DEVICE_PLANE_RE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window
    busy = kernel = merge = 0
    kernel_events = merge_events = 0
    op_time: Dict[str, int] = {}
    gaps: List[Interval] = []
    for plane in devices:
        ops = [(int(e.start_ns), int(e.end_ns), e) for e in
               _events(plane, "XLA Ops")]
        spans = union(clip(((s, e) for s, e, _ in ops), lo, hi))
        busy += sum(e - s for s, e in spans)
        edge = lo
        for s, e in spans:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if hi > edge:
            gaps.append((edge, hi))
        for s, e, ev in ops:
            if e <= lo or s >= hi:
                continue
            d = min(e, hi) - max(s, lo)
            op = ev.name.split(" = ", 1)[0]
            op_time[op] = op_time.get(op, 0) + d
            if KERNEL_RE.match(ev.name):
                kernel += d
                kernel_events += 1
        for ev in _events(plane, "XLA Modules"):
            s, e = int(ev.start_ns), int(ev.end_ns)
            if e <= lo or s >= hi:
                continue
            if MERGE_RE.search(ev.name):
                merge += min(e, hi) - max(s, lo)
                merge_events += 1
    n = len(devices)
    busy_q = union((lo + int(a * 1e9), lo + int(b * 1e9))
                   for a, b in inflight)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "kernel_s": kernel / 1e9,
        "kernel_events": kernel_events,
        "merge_s": merge / 1e9,
        "merge_events": merge_events,
        "device_ops": [[name, ns / 1e9] for name, ns in top],
        "idle_gaps": [[label_gap(g, host, busy_q), (g[1] - g[0]) / 1e9]
                      for g in longest],
    }


def label_gap(gap: Interval, host: Dict[str, Sequence[Interval]],
              inflight: Sequence[Interval]) -> str:
    """What the harness was doing in an idle gap (see the module doc)."""
    length = gap[1] - gap[0]
    served = sum(e - s for s, e in clip(inflight, *gap))
    if 2 * served < length:
        return "bench.generator.sleep"
    best, best_ns = "program", 0
    for name in HOST_SPANS:
        ns = sum(e - s for s, e in clip(union(host[name]), *gap))
        if ns > best_ns:
            best, best_ns = name, ns
    return best
