"""From a profiler trace to device busy time, kernel time and idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict of events (the form the committed test trace keeps);
``reduce`` turns that into the numbers the per-layer metrics read:

- the window: from the start of the first profiled call to the end of
  the last, on the trace's own clock (the harness's ``bench_call``
  annotations);
- busy: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices that ran any;
- kernel time: the summed device time of the operations whose name
  contains one of a lane's kernel names (``lanes/<lane>.py``);
- device ops: the device time of each operation, under its short name
  (the HLO instruction's name, and a custom call's target);
- idle gaps: the stretches of the window with no device operation, each
  labelled with the innermost host span that covers its middle.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

CALL_SPAN = "bench_call"
# Host annotations kept from the trace: the harness's call span and the
# program's RunTrace spans (``RunTrace(annotate=True)``).
HOST_SPANS = (CALL_SPAN, "segment", "chunk", "guard", "reframe")
DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"

Event = Tuple[str, int, int]   # (name, start ns, duration ns)


def extract(path: str) -> dict:
    """{"device": {plane: [event, ...]}, "host": [event, ...]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    dev: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_SPANS))
    return {"device": dev, "host": host}


def short_name(op: str) -> str:
    """``%name`` of an HLO instruction's text, with ``[target]`` for a
    custom call: the device trace names an op by its whole text."""
    name = op.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', op)
    return f"{name} [{m.group(1)}]" if m else name


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    calls: int
    kernel_s: Dict[str, float]       # lane -> device seconds
    device_ops: List[list]           # [[name, seconds], ...], top 10
    idle_gaps: List[list]            # [[label, seconds], ...], top 10


def _label(host: List[Event], t: float) -> str:
    cover = [(d, n) for n, s, d in host if s <= t <= s + d]
    return min(cover)[1] if cover else "between calls"


def reduce(trace: dict, kernels: Dict[str, List[str]]) -> Summary:
    calls = [(s, s + d) for n, s, d in trace["host"] if n == CALL_SPAN]
    if not calls:
        raise ValueError("the trace holds no profiled call")
    lo, hi = min(a for a, _ in calls), max(b for _, b in calls)
    planes = {p: [(n, max(s, lo), min(s + d, hi))
                  for n, s, d in evs if s < hi and s + d > lo]
              for p, evs in trace["device"].items()}
    planes = {p: evs for p, evs in planes.items() if evs}
    if not planes:
        raise ValueError("no device operation ran inside the profiled calls")
    k = len(planes)
    busy, ops, kern = 0.0, {}, {lane: 0.0 for lane in kernels}
    gaps = []
    for p, evs in sorted(planes.items()):
        merged = _union([(a, b) for _, a, b in evs])
        busy += sum(b - a for a, b in merged) / k
        for n, a, b in evs:
            short = short_name(n)
            ops[short] = ops.get(short, 0.0) + (b - a) / k
            for lane, names in kernels.items():
                if any(x in n for x in names):
                    kern[lane] += (b - a) / k
        if p == min(planes):
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps = [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, reverse=True)[:10]
    ns = 1e-9
    return Summary(
        window_s=(hi - lo) * ns, busy_s=busy * ns, calls=len(calls),
        kernel_s={lane: v * ns for lane, v in kern.items()},
        device_ops=[[n, v * ns] for n, v in top_ops],
        idle_gaps=[[_label(trace["host"], (a + b) / 2), d * ns]
                   for d, a, b in top_gaps])
