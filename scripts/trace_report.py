"""Render a flight-recorder JSONL (repro.telemetry.RunTrace) as a
human-readable run report.

    PYTHONPATH=src python scripts/trace_report.py RUN_TRACE.jsonl
    PYTHONPATH=src python scripts/trace_report.py --selftest

The report has three parts:
  1. the per-kind summary table (``RunTrace.summary()``);
  2. a wall-clock timeline of every span/event in start order, indented
     under the span it ran in, with the load-bearing fields of each
     record inlined;
  3. a health section: engine dispatch regimes, guard trips / reframe
     splices, chaos verdict counts, and the jit-cache delta.  Zero new compiles against a WARM cache is the
     contract; a cold first run legitimately compiles once, so a
     non-zero delta is reported loudly but only fails the exit code
     under ``--selftest`` (which warms the cache before tracing).

``--selftest`` runs a tiny traced ``run_scenario`` in-process, writes
the JSONL to a temp file, and reports on it — the CI fast-tier smoke
lane proving the whole record → export → render path end to end.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.telemetry import RunTrace  # noqa: E402

# Fields worth inlining on the timeline, per event kind.
_TIMELINE_FIELDS = {
    "engine_dispatch": ("segment", "engine", "b_pad", "n_pad", "k", "c",
                        "records", "vmem_est_bytes"),
    "scenario": ("engine",),
    "segment": ("name", "draws"),
    "segment.compile": (),
    "segment.stacks": (),
    "segment.upload": (),
    "segment.prep": ("segment",),
    "segment.splice": ("segment",),
    "guard": ("name", "segment"),
    "chunk": ("engine", "segment", "launch", "records"),
    "chunk.dispatch": (),
    "chunk.wait": (),
    "chunk.fetch": (),
    "guard_eval": ("record", "guard", "tripped"),
    "reframe": ("record", "segment", "auto", "max_shift"),
    "chaos_draw": ("draw", "verdict", "margin", "peak", "reframed"),
}


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _timeline(tr: RunTrace) -> list[str]:
    lines = ["", "timeline (t in s since trace epoch):"]
    parent = {ev.id: ev.parent for ev in tr.events if ev.id is not None}

    def depth(ev) -> int:
        d, p = 0, ev.parent
        while p is not None:
            d, p = d + 1, parent.get(p)
        return d

    for ev in sorted(tr.events, key=lambda e: e.t):
        dur = f" [{ev.dur * 1e3:8.1f} ms]" if ev.dur is not None else " " * 14
        fields = _TIMELINE_FIELDS.get(ev.kind, tuple(sorted(ev.data)))
        kv = " ".join(f"{k}={_fmt(ev.data[k])}" for k in fields
                      if k in ev.data)
        kind = "  " * depth(ev) + ev.kind
        lines.append(f"  {ev.t:9.3f}{dur}  {kind:<19} {kv}")
    return lines


def _health(tr: RunTrace, strict: bool = False) -> tuple[list[str], int]:
    """Health section lines + exit status (non-zero on hard failures).

    ``strict`` makes a non-zero compile delta fatal — correct only when
    the caller knows the cache was warm before the traced run.
    """
    lines = ["", "health:"]
    status = 0

    dispatches = tr.by_kind("engine_dispatch")
    if dispatches:
        engines = sorted({str(e.data.get("engine")) for e in dispatches})
        lines.append(f"  engines dispatched: {', '.join(engines)} "
                     f"({len(dispatches)} dispatch(es))")
    trips = [e for e in tr.by_kind("guard_eval") if e.data.get("tripped")]
    reframes = tr.by_kind("reframe")
    if tr.by_kind("guard_eval"):
        lines.append(f"  guard evals: {len(tr.by_kind('guard_eval'))}, "
                     f"tripped: {len(trips)}, reframe splices: "
                     f"{len(reframes)}")

    draws = tr.by_kind("chaos_draw")
    if draws:
        verdicts: dict[str, int] = {}
        for e in draws:
            v = str(e.data.get("verdict"))
            verdicts[v] = verdicts.get(v, 0) + 1
        lines.append("  chaos draws: " + ", ".join(
            f"{k}={v}" for k, v in sorted(verdicts.items())))

    for e in tr.by_kind("compile_stats"):
        delta = e.data.get("delta")
        if delta is None:
            continue
        new = {k: v for k, v in delta.items() if v}
        if new and strict:
            status = 1
            lines.append(f"  COMPILE-STATS VIOLATION: new compiles during "
                         f"traced warm-cache run: {new}")
        elif new:
            lines.append(f"  jit-cache delta: new compiles during traced "
                         f"run: {new} (expected once on a cold cache; a "
                         f"warm-cache replay must show 0)")
        else:
            lines.append("  jit-cache delta: 0 new compiles (contract holds)")
    return lines, status


def report(path: str, strict: bool = False) -> int:
    tr = RunTrace.from_jsonl(path)
    print(tr.summary())
    for ln in _timeline(tr):
        print(ln)
    lines, status = _health(tr, strict=strict)
    for ln in lines:
        print(ln)
    return status


def _selftest() -> int:
    """Trace a tiny scenario end to end, then report on the JSONL."""
    import numpy as np

    from repro.core import (ControllerConfig, SimConfig, fully_connected,
                            make_links)
    from repro.scenarios import FreqStep, Scenario, run_scenario

    topo = fully_connected(6)
    links = make_links(topo, cable_m=2.0)
    ppm = np.random.default_rng(0).uniform(-1, 1, topo.num_nodes)
    ppm -= ppm.mean()

    def go(**kw):
        return run_scenario(
        topo, links, ControllerConfig(kp=2e-7), ppm.astype(np.float32),
        Scenario(events=(FreqStep(t=0.036, nodes=(1,), delta_ppm=0.02),),
                 name="trace-selftest"),
            SimConfig(dt=1e-3, steps=96, record_every=12),
            engine="fused", record_watermarks=True, **kw)

    go()  # warm the jit cache: the traced replay must add ZERO compiles
    res = go(trace=True)
    assert res.trace is not None and len(res.trace) > 0
    assert res.watermarks is not None
    with tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False) as f:
        path = f.name
    try:
        res.trace.to_jsonl(path)
        status = report(path, strict=True)
    finally:
        os.unlink(path)
    print(f"\nselftest: traced run_scenario round-tripped "
          f"{len(res.trace)} events; peak |beta| = "
          f"{float(res.watermarks.peak_beta):.3f} frames at record "
          f"{int(res.watermarks.peak_time_record)}")
    return status


def main() -> int:
    ap = argparse.ArgumentParser(
        description="render a bittide-run-trace JSONL")
    ap.add_argument("path", nargs="?", help="trace JSONL to report on")
    ap.add_argument("--selftest", action="store_true",
                    help="trace a tiny run_scenario in-process and report it")
    args = ap.parse_args()
    if args.selftest:
        return _selftest()
    if not args.path:
        ap.error("need a trace path (or --selftest)")
    return report(args.path)


if __name__ == "__main__":
    sys.exit(main())
