"""RunTrace spans with parents, totals, self times and byte counters,
and the spans ``run_scenario`` opens on every kernel lane.

1. The recorder: parents from the open-span stack, ``totals()`` and
   ``self_times()`` on a hand-built trace, counters, ``note()``, the
   JSONL round trip with the counters in the header, files written
   before spans carried ids, and the kept annotated recorders.
2. The null recorder: one shared no-op context, no-op counters.
3. A traced FC8 scenario (re-established LatencyStep, explicit graph
   Reframe, guard on) emits every span kind on each kernel lane; each
   ``chunk``'s dispatch / wait / fetch children lie inside it.
4. ``h2d_bytes`` equals the bytes of the padded shapes, on the call
   that builds the fabric's segment plan and on a call that reuses it,
   ``d2h_bytes`` those of the unpadded ones, and ``d2h_reads`` one read
   a chunk and one a state read; the unpadded reads equal the padded
   lane's.
5. With ``annotate=True`` a ``jax.profiler`` capture holds one host
   event per span, with its name, at the span's start mapped onto the
   profiler clock.
"""
import collections
import pathlib

import jax
import numpy as np
import pytest

from engine_harness import zero_mean_ppm
from repro.core import (ControllerConfig, ReframePolicy, SimConfig,
                        fully_connected, make_links)
from repro.kernels import EngineOptions, simulate_ensemble_dense
from repro.kernels.bittide_step import SUBLANE, TILE
from repro.scenarios import (LatencyStep, Reframe, Scenario, edges_between,
                             run_scenario)
from repro.scenarios.plan import _clear_plans
from repro.telemetry import NULL_TRACE, RunTrace, Telemetry, TraceEvent
from repro.telemetry import trace as trace_mod
from repro.telemetry.trace import annotation_name

KERNEL_LANES = ("fused", "tiled", "per-step", "sparse")
RUN_KINDS = {"scenario", "segment.compile", "segment.stacks",
             "segment.upload", "segment.prep", "segment.splice", "guard",
             "reframe", "chunk", "chunk.dispatch", "chunk.wait",
             "chunk.fetch"}


@pytest.fixture(autouse=True)
def _cold_plans():
    """Every test starts with no segment plan, so what a run builds and
    uploads does not depend on which tests this worker ran before."""
    _clear_plans()


# ------------------------------------------------------------ 1. recorder

def test_spans_record_their_parents_and_notes():
    tr = RunTrace()
    with tr.span("scenario"):
        with tr.span("chunk", engine="fused"):
            tr.event("guard_eval", record=3)
            with tr.span("chunk.fetch"):
                tr.note(bytes=7)
        with tr.span("reframe", record=4):
            tr.note(max_shift=2)
    tr.note(ignored=True)                   # no span open: nothing to do
    by = {e.kind: e for e in tr.events}
    assert by["scenario"].parent is None
    assert by["chunk"].parent == by["scenario"].id
    assert by["chunk.fetch"].parent == by["chunk"].id
    assert by["reframe"].parent == by["scenario"].id
    assert by["guard_eval"].parent == by["chunk"].id
    assert by["guard_eval"].id is None and by["guard_eval"].dur is None
    assert len({e.id for e in tr.events if e.dur is not None}) == 4
    assert by["chunk.fetch"].data == {"bytes": 7}
    assert by["reframe"].data == {"record": 4, "max_shift": 2}
    # Children close first; each lies inside its parent.
    assert [e.kind for e in tr.events if e.dur is not None] == [
        "chunk.fetch", "chunk", "reframe", "scenario"]
    for e in tr.events:
        if e.parent is not None and e.dur is not None:
            p = next(x for x in tr.events if x.id == e.parent)
            assert p.t <= e.t and e.t + e.dur <= p.t + p.dur + 1e-9


def test_totals_and_self_times_on_a_hand_built_trace():
    tr = RunTrace()
    tr.events = [
        TraceEvent("chunk.dispatch", 0.1, 0.2, id=1, parent=0),
        TraceEvent("chunk.wait", 0.3, 0.5, id=2, parent=0),
        TraceEvent("chunk", 0.0, 1.0, id=0, parent=9),
        TraceEvent("chunk.dispatch", 2.1, 0.1, id=4, parent=3),
        TraceEvent("chunk", 2.0, 0.5, id=3, parent=9),
        TraceEvent("guard_eval", 1.5, parent=9),
        TraceEvent("segment.prep", 1.2, 0.25, id=5, parent=9),
        TraceEvent("scenario", 0.0, 3.0, id=9),
    ]
    assert tr.totals() == pytest.approx({
        "chunk": 1.5, "chunk.dispatch": 0.3, "chunk.wait": 0.5,
        "segment.prep": 0.25, "scenario": 3.0})
    assert tr.self_times() == pytest.approx({
        "chunk": 1.5 - 0.8, "chunk.dispatch": 0.3, "chunk.wait": 0.5,
        "segment.prep": 0.25, "scenario": 3.0 - 1.75})


def test_counters_and_jsonl_round_trip(tmp_path):
    tr = RunTrace(name="count")
    tr.count("h2d_bytes", 4096)
    tr.count("h2d_bytes", np.int64(4))
    tr.count("d2h_bytes", 12)
    with tr.span("chunk", engine="fused"):
        with tr.span("chunk.wait"):
            pass
    assert tr.counters == {"h2d_bytes": 4100, "d2h_bytes": 12}
    assert "counter h2d_bytes: 4100" in tr.summary()
    p = tmp_path / "t.jsonl"
    tr.to_jsonl(str(p))
    back = RunTrace.from_jsonl(str(p))
    assert back.counters == tr.counters
    assert back.clock_ns == tr.clock_ns
    assert [(e.kind, e.id, e.parent) for e in back.events] == [
        (e.kind, e.id, e.parent) for e in tr.events]
    assert back.totals() == pytest.approx(tr.totals(), abs=1e-6)


def test_files_without_ids_still_load(tmp_path):
    """A schema bittide-run-trace/1 file written before spans carried
    ids or the header carried counters."""
    p = tmp_path / "old.jsonl"
    p.write_text('{"schema": "bittide-run-trace/1", "name": "old", '
                 '"epoch": 1.0}\n'
                 '{"kind": "chunk", "t": 0.5, "dur": 0.25}\n'
                 '{"kind": "reframe", "t": 0.8, "data": {"record": 3}}\n')
    tr = RunTrace.from_jsonl(str(p))
    assert tr.counters == {} and tr.clock_ns is None
    assert tr.totals() == {"chunk": 0.25}
    assert tr.self_times() == {"chunk": 0.25}
    assert tr.events[1].parent is None


def test_profiler_clock_mapping():
    tr = RunTrace()
    assert tr.profiler_ns(0.0) == tr.clock_ns
    assert tr.profiler_ns(1.5) - tr.clock_ns == 1_500_000_000


def test_annotated_recorders_are_kept_newest_last(monkeypatch):
    ring = collections.deque(maxlen=3)
    monkeypatch.setattr(trace_mod, "_PROFILED", ring)
    plain = RunTrace(name="plain")
    made = [RunTrace(name=f"p{i}", annotate=True) for i in range(4)]
    kept = trace_mod.profiled_traces()
    assert kept == made[1:]                 # bounded, oldest dropped
    assert plain not in kept
    assert trace_mod.PROFILED_MAX >= 1024   # a whole benchmark window


# ------------------------------------------------------- 2. null recorder

def test_null_trace_spans_and_counts_do_nothing():
    a, b = NULL_TRACE.span("chunk", engine="x"), NULL_TRACE.span("guard")
    assert a is b                           # one shared no-op context
    with a:
        with b:                             # re-entrant
            NULL_TRACE.note(max_shift=1)
    assert NULL_TRACE.count("h2d_bytes", 10) is None
    assert not hasattr(NULL_TRACE, "counters")
    assert NULL_TRACE.events == []


# ------------------------------------------- 3. the runner's span kinds

def _fc8_scenario(b=2):
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-7)
    ppm = np.stack([zero_mean_ppm(8, 0.5, seed=s) for s in range(b)])
    scen = Scenario(events=(
        LatencyStep(t=0.048, edges=edges_between(topo, 0, 2),
                    cable_m=1000.0, reestablish=True),
        Reframe(t=0.096, mode="graph"),
    ))
    cfg = SimConfig(dt=1e-3, steps=144, record_every=12)
    return topo, links, ctrl, ppm.astype(np.float32), scen, cfg


def _traced_run(engine, guard=True, b=2):
    topo, links, ctrl, ppm, scen, cfg = _fc8_scenario(b)
    tr = RunTrace(name=engine)
    res = run_scenario(
        topo, links, ctrl, ppm, scen, cfg,
        options=EngineOptions(engine=engine),
        telemetry=Telemetry(beta=True, watermarks=True, trace=tr,
                            guard=ReframePolicy(depth=32) if guard
                            else None))
    return res, tr


@pytest.mark.parametrize("engine", KERNEL_LANES)
def test_traced_run_emits_every_span_kind(engine):
    res, tr = _traced_run(engine)
    assert res.engine == engine
    kinds = {e.kind for e in tr.events if e.dur is not None}
    assert RUN_KINDS <= kinds, RUN_KINDS - kinds
    (scen,) = tr.by_kind("scenario")
    spans = {e.id: e for e in tr.events if e.dur is not None}
    top = {"segment.compile", "segment.stacks", "segment.prep",
           "segment.splice", "guard", "reframe", "chunk"}
    for e in tr.events:
        if e.kind in top:
            assert e.parent == scen.id, e
        if e.kind == "segment.upload":
            assert spans[e.parent].kind == "segment.stacks"
    # The explicit Reframe is a span now, with the data of the old event.
    explicit = [e for e in tr.by_kind("reframe") if not e.data["auto"]]
    assert len(explicit) == 1
    assert explicit[0].data["record"] == 8
    assert explicit[0].data["max_shift"] >= 0
    # Every chunk holds its three children, inside it, summing to at
    # most its duration.
    chunks = tr.by_kind("chunk")
    assert len(chunks) == res.num_launches
    for ch in chunks:
        kids = [e for e in tr.events if e.parent == ch.id]
        assert {k.kind for k in kids} == {"chunk.dispatch", "chunk.wait",
                                          "chunk.fetch"}
        for k in kids:
            assert ch.t <= k.t and k.t + k.dur <= ch.t + ch.dur + 1e-9
        assert sum(k.dur for k in kids) <= ch.dur + 1e-9
    # The self times add back up to the scenario span.
    assert sum(tr.self_times().values()) == pytest.approx(
        scen.dur, rel=1e-6, abs=1e-6)


def test_segment_sum_keeps_one_chunk_span():
    topo, links, ctrl, ppm, scen, cfg = _fc8_scenario()
    res = run_scenario(topo, links, ctrl, ppm, scen, cfg,
                       options=EngineOptions(engine="segment-sum"),
                       telemetry=Telemetry(beta=True, trace=True))
    kinds = {e.kind for e in res.trace.events}
    assert {"scenario", "segment.compile", "segment.splice", "reframe",
            "chunk"} <= kinds
    assert not kinds & {"chunk.dispatch", "chunk.wait", "chunk.fetch",
                        "segment.stacks"}


def test_compiled_scenario_skips_the_compile_span():
    topo, links, ctrl, ppm, scen, cfg = _fc8_scenario()
    first = run_scenario(topo, links, ctrl, ppm, scen, cfg,
                         options=EngineOptions(engine="fused"))
    res = run_scenario(topo, links, ctrl, ppm, scen, cfg,
                       options=EngineOptions(engine="fused"),
                       compiled=first.compiled,
                       telemetry=Telemetry(trace=True))
    assert not res.trace.by_kind("segment.compile")
    np.testing.assert_array_equal(res.freq_ppm, first.freq_ppm)


# ------------------------------------------------------ 4. byte counters

@pytest.mark.parametrize("path", ["build", "hit"])
@pytest.mark.parametrize("engine", ["fused", "sparse"])
def test_transfer_counters_match_unpadded_shapes(engine, path):
    """No guard: the counts follow from the shapes alone.  Two segments
    with different latency tables (two stacks or slot tables), three
    chunks of 4 records, one re-establish and one explicit Reframe, each
    reading the live state once.  Uploads are padded; read-backs carry
    the unpadded (b, n) slices only, one read a chunk and one a state
    read, and the lanes read no gains back.

    Every call uploads ν_u once (no segment changes it) and the per-draw
    λeff folds after the re-establish and the Reframe.  The call that
    builds the plan also uploads what the plan keeps: the stacks or slot
    tables, one mask, the gains and the latency-class rows for the whole
    scenario, and segment 0's fold of β0.  The same call again hits the
    plan and uploads nothing else."""
    b = 2
    if path == "hit":
        _traced_run(engine, guard=False, b=b)
    res, tr = _traced_run(engine, guard=False, b=b)
    n = res.topo.num_nodes
    n_pad, b_pad = TILE, SUBLANE
    state = b_pad * n_pad * 4                    # one (B_pad, N_pad) f32
    real = b * n * 4                             # one (b, n) f32
    disp = tr.by_kind("engine_dispatch")
    segs = len(disp)
    assert segs == 3 and res.num_launches == 3
    records = [c.data["records"] for c in tr.by_kind("chunk")]
    h2d = 3 * state                                     # ν_u, two folds
    if path == "hit":
        plan = {"prep_plan_hits": 1}
    elif engine == "fused":
        plan = {"prep_plan_builds": 1}
        c = disp[0].data["c"]
        e = res.topo.num_edges
        h2d += (2 * 4 * e * 4 + c * 4                   # edge lists, λ dummy
                + n_pad * 4 + 2 * b_pad * 4             # mask, gains
                + b_pad * c * 4 + state)                # classes, fold of β0
    else:
        plan = {"prep_plan_builds": 1}
        k = disp[0].data["k"]
        h2d += (k * n_pad * 4 + 2 * 2 * k * n_pad * 4   # nbr; latf, w ×2
                + n_pad * 4 + 2 * b_pad * 4 + state)    # mask, gains, fold
    d2h = (sum(2 * r * real for r in records)           # freq, β
           + res.num_launches * 4 * real                # watermarks
           + 2 * 2 * real                               # splice, Reframe
           + 2 * real)                                  # final ψ, ν
    reads = res.num_launches + 2 + 1
    assert tr.counters == {"h2d_bytes": h2d, "d2h_bytes": d2h,
                           "d2h_reads": reads, **plan}


def test_untraced_run_matches_traced_run():
    a, _ = _traced_run("fused", guard=False)
    topo, links, ctrl, ppm, scen, cfg = _fc8_scenario()
    b = run_scenario(topo, links, ctrl, ppm, scen, cfg,
                     options=EngineOptions(engine="fused"),
                     telemetry=Telemetry(beta=True, watermarks=True))
    assert b.trace is None
    np.testing.assert_array_equal(a.freq_ppm, b.freq_ppm)
    np.testing.assert_array_equal(a.beta, b.beta)


def _padded_fc8(b=5):
    """FC8 at B = 5: padded on both axes (B to 8 rows, N to 128 lanes)."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-7)
    ppm = np.stack([zero_mean_ppm(8, 0.5, seed=10 + s) for s in range(b)])
    cfg = SimConfig(dt=1e-3, steps=96, record_every=12)
    return topo, links, ctrl, ppm.astype(np.float32), cfg


def test_unpadded_read_back_matches_the_ensemble_lane_bit_for_bit():
    """The device slice moves where the padding is cut, not what is
    read: freq, β, the four watermark fields, ψ and ν equal the padded
    reads of ``simulate_ensemble_dense`` bit for bit."""
    topo, links, ctrl, ppm, cfg = _padded_fc8()
    tr = RunTrace()
    res = run_scenario(topo, links, ctrl, ppm, Scenario(events=()), cfg,
                       options=EngineOptions(engine="fused"),
                       telemetry=Telemetry(beta=True, watermarks=True,
                                           trace=tr))
    ref = simulate_ensemble_dense(
        topo, links, ppm, steps=cfg.steps, kp=ctrl.kp, dt=cfg.dt,
        record_every=cfg.record_every,
        options=EngineOptions(engine="fused"),
        telemetry=Telemetry(beta=True, watermarks=True))
    assert res.engine == ref.engine == "fused"
    assert tr.counters["d2h_reads"] == res.num_launches + 1
    np.testing.assert_array_equal(res.freq_ppm, ref[0])
    np.testing.assert_array_equal(res.beta, ref.beta)
    np.testing.assert_array_equal(res.psi, ref[1])
    np.testing.assert_array_equal(res.nu, ref.nu)
    for f in ("beta_abs_max", "peak_record", "nu_min_ppm", "nu_max_ppm"):
        np.testing.assert_array_equal(getattr(res.watermarks, f),
                                      getattr(ref.watermarks, f))


@pytest.mark.parametrize("engine", ["fused", "sparse"])
def test_unpadded_splice_reads_once_a_chunk(engine):
    """A re-established LatencyStep at B = 5: one read a chunk, one at
    the splice, one at the end; traced and untraced runs agree."""
    topo, links, ctrl, ppm, cfg = _padded_fc8()
    scen = Scenario(events=(LatencyStep(
        t=0.048, edges=edges_between(topo, 0, 2), cable_m=1000.0,
        reestablish=True),))
    kw = dict(options=EngineOptions(engine=engine))
    tr = RunTrace()
    a = run_scenario(topo, links, ctrl, ppm, scen, cfg,
                     telemetry=Telemetry(beta=True, watermarks=True,
                                         trace=tr), **kw)
    b = run_scenario(topo, links, ctrl, ppm, scen, cfg,
                     telemetry=Telemetry(beta=True, watermarks=True), **kw)
    assert a.engine == engine and a.freq_ppm.shape == (5, 8, 8)
    assert len(tr.by_kind("chunk")) == a.num_launches == 2
    assert tr.counters["d2h_reads"] == a.num_launches + 2
    for f in ("freq_ppm", "beta", "psi", "nu"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    np.testing.assert_array_equal(a.watermarks.beta_abs_max,
                                  b.watermarks.beta_abs_max)


# ------------------------------------------- 5. the profiler's own clock

def _capture_start_ns(space_stats) -> int:
    return dict(space_stats)["profile_start_time"]


def test_profiler_capture_holds_every_span(tmp_path):
    from jax.profiler import ProfileData
    topo, links, ctrl, ppm, scen, cfg = _fc8_scenario()
    kw = dict(options=EngineOptions(engine="fused"))
    run_scenario(topo, links, ctrl, ppm, scen, cfg, **kw)   # compile first
    tr = RunTrace(name="prof", annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_scenario(topo, links, ctrl, ppm, scen, cfg,
                     telemetry=Telemetry(trace=tr), **kw)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(xplane))
    (env,) = [p for p in data.planes if p.name == "Task Environment"]
    origin = _capture_start_ns(env.stats)
    names = {annotation_name(e.kind, e.data) for e in tr.events
             if e.dur is not None}
    host = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        host.setdefault(ev.name, []).append(
                            origin + int(ev.start_ns))
    spans = [e for e in tr.events if e.dur is not None]
    assert sum(len(v) for v in host.values()) == len(spans)
    for e in spans:
        starts = host[annotation_name(e.kind, e.data)]
        gap = min(abs(s - tr.profiler_ns(e.t)) for s in starts)
        assert gap < 1_000_000, (e.kind, gap)
