"""The program's own spans and counters for the window's calls.

A ``--trace 1`` run hands every call, the warm-up's first, a fresh
``RunTrace(annotate=True)``, and the program keeps the newest of those
(``repro.telemetry.trace.profiled_traces``): the window's calls are the
last ``len(readings.calls)`` of them.  Where the calls ran untraced, or
the program keeps no such recorders, a reader gets None and its metric
is left out of the line.
"""
from __future__ import annotations

import sys
from typing import Iterable, List, Optional


def window_traces(r) -> Optional[List[object]]:
    """The window's RunTraces, oldest first, or None."""
    if all(c["launch_s"] is None for c in r.calls):
        return None
    kept = getattr(sys.modules.get("repro.telemetry.trace"),
                   "profiled_traces", None)
    traces = kept()[-len(r.calls):] if kept is not None else []
    return traces or None


def span_ms(r, kinds: Iterable[str]) -> Optional[float]:
    """Mean over the window's calls of the summed durations of the span
    ``kinds``, in ms (0 where no call has them)."""
    traces = window_traces(r)
    if traces is None:
        return None
    return 1e3 * sum(t.totals().get(k, 0.0)
                     for t in traces for k in kinds) / len(traces)


def counter_mb(r, name: str) -> Optional[float]:
    """Mean over the window's calls of the counter ``name``, in MB (1e6
    bytes)."""
    traces = window_traces(r)
    if traces is None:
        return None
    return sum(t.counters.get(name, 0) for t in traces) / len(traces) / 1e6


def outer_span_ms(r, kinds: Iterable[str],
                  nest: Iterable[str]) -> Optional[float]:
    """As ``span_ms``, counting a span of ``kinds`` only where no span of
    ``nest`` encloses it: metrics whose kinds may nest in each other then
    count no time twice."""
    traces = window_traces(r)
    if traces is None:
        return None
    kinds, nest = set(kinds), set(nest)
    total = 0.0
    for t in traces:
        spans = {e.id: e for e in t.events
                 if e.dur is not None and e.id is not None}
        for e in t.events:
            if e.dur is None or e.kind not in kinds:
                continue
            up = spans.get(e.parent)
            while up is not None and up.kind not in nest:
                up = spans.get(up.parent)
            if up is None:
                total += e.dur
    return 1e3 * total / len(traces)
