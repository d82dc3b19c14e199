"""One benchmark run of one cell: set-up, measured window, check, result.

    python3 benchmarks/chip/run.py --workload NAME --seed N --seconds S \
        --trace 0|1

Set-up builds the program's fabric for the cell and warms every shape the
window uses with one call on draws of its own; it ends where the window
starts.  The window is a closed loop with one client: calls run back to
back until ``--seconds`` have passed, and a call that starts inside the
window runs to its end and counts.  Each call is timed from its start
until its results are on the host (``run_scenario`` returns host
arrays).

``--trace 0`` reports the cell's end-to-end metrics, taken with tracing
off.  ``--trace 1`` threads ``RunTrace(annotate=True)`` through every call,
profiles the device over the first ``PROFILE_S`` seconds of the window,
and reports the per-layer metrics, ``busy_s``/``window_s`` and a
``breakdown``.

After the window closes and the device's peak memory is read, the
reference re-runs a sample of the window's calls, drawn from the seed,
and the comparison decides ``correct``; in a cell whose mix has a
guard, the reference replays the guard decisions it cannot tell
(``compare.Replay``) and the run prints, per compared call, how many it
held and how many it replayed.  Each compared number is printed
beside its limit, as the last lines of standard error and as the last
key (``checks``) of the result line, which is the last line of standard
output.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, and when the program or a cell file is missing.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import compare, generator, reference
from .spec import Spec

ROOT = Path(__file__).resolve().parents[3]
PROFILE_S = 3.0


class _CompileCount:
    """Programs JAX has fetched (compiled, or loaded from the persistent
    cache) and persistent-cache hits, from the moment this is made."""

    FETCH = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.n = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_fetch)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_fetch(self, event, duration, **_kw):
        if event == self.FETCH:
            self.n += 1

    def _on_event(self, event, **_kw):
        if event == self.HIT:
            self.hits += 1


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU found (JAX's first device is "
                       f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where that
    is set, else at a fixed directory inside the checkout (the path is
    part of the cache key, so it never moves)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@dataclasses.dataclass
class Readings:
    """What a per-layer metric reader (``metrics/<name>.py``) may read."""

    calls: List[dict]                 # wall_s, launch_s, launches,
                                      # reframes per call
    lane: str                         # the engine lane the results report
    shape: dict                       # draws, nodes, classes, periods, ...
    peaks: dict                       # this device's row of peaks.json
    count: Callable[[str], dict]      # lane -> {"flops", "bytes"} per call
    profile: Optional[object] = None  # trace.Summary of the profiled calls


class _Sample:
    """A uniform sample of ``k`` of the window's calls (reservoir),
    drawn from the seed: {call index: answer}."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng([int(seed) % 2**64, 7])
        self.kept: Dict[int, dict] = {}
        self.seen = 0

    def offer(self, call: int, answer: Callable[[], dict]) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept[call] = answer()
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            del self.kept[sorted(self.kept)[j]]
            self.kept[call] = answer()


def _shape(config: dict, traffic: dict, kinds) -> dict:
    lats = {reference.cable_frames(config, config["cable_m"])}
    lats |= {reference.cable_frames(config, ev["cable_m"])
             for ev in traffic.get("events", []) if "cable_m" in ev}
    periods = reference.periods_of(config)
    tel = traffic["telemetry"]
    return {"draws": int(traffic["draws"]),
            "nodes": reference.topology_nodes(config["topology"], kinds),
            "edges": len(reference.build_fabric(config, kinds).src),
            "classes": len(lats), "periods": periods,
            "records": periods // int(traffic["record_every"]),
            "measure": bool(tel["beta"] or tel["watermarks"])}


def run_cell(spec: Spec, name: str, seed: int, seconds: float, trace: bool,
             t0: float, device: dict, log=sys.stderr) -> dict:
    """Set up, measure and check one cell; returns the result line."""
    import jax

    from . import program as prog_mod
    from . import trace as trace_mod
    cell = spec.cell(name)
    cfg, tr, kinds = cell.config, cell.traffic, cell.kinds
    prog = prog_mod.Program(cfg, tr, kinds)
    shape = _shape(cfg, tr, kinds)
    work = generator.work_per_call(cfg, tr, kinds)

    def new_trace():
        from repro.telemetry import RunTrace
        return RunTrace(name=name, annotate=True) if trace else None

    # Set-up: one warm-up call on draws of its own compiles (or loads
    # from the persistent cache) every program the window calls.
    compiles = _CompileCount()
    warm = prog.call(generator.draws(cfg, tr, kinds, seed,
                                     generator.WARMUP_CALL),
                     trace=new_trace())
    lane = warm.engine
    del warm
    n_setup_compiles, n_setup_hits = compiles.n, compiles.hits
    setup_s = time.perf_counter() - t0

    sample = _Sample(int(tr["check_calls"]), seed)
    calls: List[dict] = []
    prof_dir = tempfile.mkdtemp(prefix="chipbench_") if trace else None
    profiling = False
    try:
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            k = len(calls)
            ppm = generator.draws(cfg, tr, kinds, seed, k)
            rt = new_trace()
            if trace and k == 0:
                jax.profiler.start_trace(prof_dir)
                profiling = True
            t = time.perf_counter()
            if trace:
                with jax.profiler.TraceAnnotation(trace_mod.CALL_SPAN):
                    res = prog.call(ppm, trace=rt)
            else:
                res = prog.call(ppm)
            wall = time.perf_counter() - t
            launch = (sum(e.dur for e in rt.by_kind("chunk"))
                      if rt is not None else None)
            calls.append({"wall_s": wall, "launch_s": launch,
                          "launches": int(res.num_launches),
                          "reframes": len(res.reframes)})
            if res.engine != lane:
                raise RuntimeError(f"call {k} ran on lane {res.engine!r}, "
                                   f"the warm-up on {lane!r}")
            sample.offer(k, lambda: prog.answer(res))
            del res
            if profiling and (time.perf_counter() - start >= PROFILE_S):
                jax.profiler.stop_trace()
                profiling = False
        end = start + sum(c["wall_s"] for c in calls)
        window_wall = time.perf_counter() - start
        if profiling:
            jax.profiler.stop_trace()
            profiling = False
        mem = memory_peak_bytes()
        window_compiles = compiles.n - n_setup_compiles
        summary = None
        if trace:
            xplanes = sorted(Path(prof_dir).rglob("*.xplane.pb"))
            if not xplanes:
                raise RuntimeError("the profiler wrote no trace")
            summary = trace_mod.reduce(
                trace_mod.extract(str(xplanes[-1])),
                {n: m.TRACE_NAMES for n, m in spec.lanes().items()})
    finally:
        if profiling:
            jax.profiler.stop_trace()
        if prof_dir:
            shutil.rmtree(prof_dir, ignore_errors=True)
    del prog

    # Correctness, after the window and the memory reading: the
    # reference re-runs the sampled calls from their inputs, and where
    # the mix has a guard replays the decisions it cannot tell.
    per_call, decisions = [], {}
    check_t0 = time.perf_counter()
    for k, got in sorted(sample.kept.items()):
        replay = compare.Replay(got, cell.limits) if tr.get("guard") else None
        ref = reference.simulate(cfg, tr, kinds,
                                 generator.draws(cfg, tr, kinds, seed, k),
                                 replay=replay)
        per_call.append(compare.gaps(got, ref, replay))
        if replay is not None:
            decisions[k] = dict(replay.counts)
    correct, failed, checks = compare.judge(per_call, cell.limits)
    check_s = time.perf_counter() - check_t0

    dev = dict(device)
    dev["memory_peak_bytes"] = mem
    line = {"correct": bool(correct), "attempted": len(calls),
            "failed": int(failed)}
    walls = np.asarray([c["wall_s"] for c in calls])
    if not trace:
        values = {
            "node_periods_per_s": work * len(calls) / window_wall,
            "draws_per_s": shape["draws"] * len(calls) / window_wall,
            "scenario_p95_ms": float(np.percentile(walls, 95)) * 1e3,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        peaks_all = spec.load_json("", "peaks.json")
        if device["kind"] not in peaks_all:
            raise KeyError(f"no peaks for device kind {device['kind']!r} "
                           "in peaks.json")
        lanes = spec.lanes()

        def count(lane_name: str) -> dict:
            return lanes[lane_name].count(shape)

        readings = Readings(calls=calls, lane=lane, shape=shape,
                            peaks=peaks_all[device["kind"]], count=count,
                            profile=summary)
        metrics = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"]).read(readings)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
    line["metrics"] = metrics
    line["device"] = dev
    if decisions:
        line["decisions"] = decisions
    line["checks"] = checks

    print(json.dumps({"cell": name, "lane": lane, "calls": len(calls),
                      "window_wall_s": window_wall,
                      "calls_wall_s": end - start,
                      "call_s_min_median_max": [
                          float(np.min(walls)), float(np.median(walls)),
                          float(np.max(walls))],
                      "launches_per_call": sorted(
                          {c["launches"] for c in calls}),
                      "reframes_per_call": sorted(
                          {c["reframes"] for c in calls}),
                      "check_s": check_s,
                      "compared_calls": sorted(sample.kept),
                      "setup_compiles": n_setup_compiles,
                      "setup_cache_hits": n_setup_hits,
                      "window_compiles": window_compiles}), file=log)
    for k, d in decisions.items():
        print(f"decisions call {k}: " + ", ".join(
            f"{n} {v}" for n, v in d.items()), file=log)
    for n, c in checks.items():
        print(f"check {n} {c['value']!r} limit {c['limit']!r}", file=log)
    return line


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from . import program as prog_mod
    prog_mod.add_to_path(ROOT)
    import repro  # noqa: F401  -- fail before the device is touched
    try:
        device = device_info(cell.chips)
    except NoDevice as e:
        print(f"chipbench: {e}; the benchmark runs only on a TPU",
              file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    line = run_cell(spec, args.workload, args.seed, args.seconds,
                    bool(args.trace), t0, device)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
