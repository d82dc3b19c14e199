"""The readers of the program's span and transfer metrics, on hand-made
``Readings`` and the program's kept ``RunTrace(annotate=True)``
recorders: a value from the window's recorders, None when the calls ran
untraced or the program keeps no recorders, 0 when a kind is absent,
and ``<name>.mc`` read by the same file."""
import pytest

import _chipbench_path  # noqa: F401
from chipbench.harness import Readings
from chipbench.spec import Spec
from repro.telemetry import trace as trace_mod

# reader -> ({kind or counter: value} of two calls, expected mean)
SPAN_READERS = {
    "stacks_ms_per_call": ([{"segment.stacks": 1.2}, {"segment.stacks": 1.4}],
                           1300.0),
    "prep_ms_per_call": ([{"segment.compile": 0.001, "segment.prep": 0.002},
                          {"segment.prep": 0.003}], 3.0),
    "splice_ms_per_call": ([{"segment.splice": 0.001, "guard": 0.002,
                             "reframe": 0.003}, {"reframe": 0.004}], 5.0),
    "dispatch_ms_per_call": ([{"chunk.dispatch": 0.0002},
                              {"chunk.dispatch": 0.0004}], 0.3),
    "wait_ms_per_call": ([{"chunk.wait": 0.2}, {"chunk.wait": 0.19}],
                         195.0),
    "fetch_ms_per_call": ([{"chunk.fetch": 0.005}, {"chunk.fetch": 0.007}],
                          6.0),
}
COUNT_READERS = {
    "h2d_mb_per_call": ([{"h2d_bytes": 463_000_000},
                         {"h2d_bytes": 463_400_000}], 463.2),
    "d2h_mb_per_call": ([{"d2h_bytes": 27_000_000},
                         {"d2h_bytes": 27_600_000}], 27.3),
}
READERS = sorted(SPAN_READERS) + sorted(COUNT_READERS)
# Kinds the readers must not count: parents and children of theirs.
OTHER = {"scenario": 9.0, "chunk": 9.0, "segment.upload": 9.0,
         "engine_dispatch": 9.0}
KINDS = sorted({k for per_call, _ in SPAN_READERS.values()
                for s in per_call for k in s})


def _readings(calls):
    return Readings(calls=calls, lane="fused", shape={}, peaks={},
                    count=lambda lane: {})


def _call(spans=None, counts=None):
    """One traced call: its recorder is made (and kept by the program)
    the way the harness makes it, with the given span totals, in
    seconds, and counters."""
    rt = trace_mod.RunTrace(name="cell", annotate=True)
    for kind, dur in dict(OTHER, **(spans or {})).items():
        rt.events.append(trace_mod.TraceEvent(kind=kind, t=0.0, dur=dur))
    for name, n in (counts or {}).items():
        rt.count(name, n)
    return {"wall_s": 1.0, "launch_s": 0.5, "launches": 1}


def _calls(name):
    """A warm-up call the readers must skip, then the window's calls."""
    _call(spans={k: 99.0 for k in KINDS}, counts={"h2d_bytes": 9e9,
                                                 "d2h_bytes": 9e9})
    if name in SPAN_READERS:
        per_call, want = SPAN_READERS[name]
        return [_call(spans=s) for s in per_call], want
    per_call, want = COUNT_READERS[name]
    return [_call(counts=c) for c in per_call], want


@pytest.mark.parametrize("name", READERS)
def test_reads_a_value_when_spans_are_present(name):
    calls, want = _calls(name)
    got = Spec(_chipbench_path.ROOT).reader(name).read(_readings(calls))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_none_when_the_calls_carry_no_spans(name, monkeypatch):
    reader = Spec(_chipbench_path.ROOT).reader(name)
    _calls(name)
    plain = [{"wall_s": 1.0, "launch_s": None, "launches": 1}] * 3
    assert reader.read(_readings(plain)) is None
    # A program that keeps no recorders, as before it kept them.
    calls, _ = _calls(name)
    monkeypatch.delattr(trace_mod, "profiled_traces")
    assert reader.read(_readings(calls)) is None


@pytest.mark.parametrize("name", READERS)
def test_zero_when_the_kind_is_absent(name):
    reader = Spec(_chipbench_path.ROOT).reader(name)
    _calls(name)
    calls = [_call(), _call()]
    assert reader.read(_readings(calls)) == 0


@pytest.mark.parametrize("name", READERS)
def test_mc_name_resolves_to_the_same_file(name):
    spec = Spec(_chipbench_path.ROOT)
    assert spec.reader(name + ".mc").__file__ == spec.reader(name).__file__
    calls, want = _calls(name)
    assert spec.reader(name + ".mc").read(_readings(calls)) == \
        pytest.approx(want)


def test_every_new_metric_is_in_the_benchmark_once_per_cell():
    spec = Spec(_chipbench_path.ROOT)
    per_layer = {m["name"]: m for m in spec.data["per_layer"]}
    for name in READERS:
        plain, mc = per_layer[name], per_layer[name + ".mc"]
        assert plain["moves"] == "node_periods_per_s"
        assert mc["moves"] == "draws_per_s"
        assert plain["source"] == mc["source"] == "program_span"
        assert plain["layer"] == mc["layer"] in ("host prep and splices",
                                                 "engine launch")
    torus = {m["name"] for m in spec.cell("torus22.free").per_layer}
    testbed = {m["name"] for m in spec.cell("testbed.splice_mc").per_layer}
    assert set(READERS) <= torus and not set(READERS) & testbed
    assert {n + ".mc" for n in READERS} <= testbed


# The guard's two readers, on the RunTrace of one guarded call of the
# Fig-18 torus (margin 1 frame: 9 rotations, 10 launches) recorded on a
# TPU v5e (``data/guard_runtrace.jsonl``).  No cell lists them yet: they
# wait for a guarded deployment.
GUARD_READERS = ("guard_ms_per_call", "reframe_ms_per_call")
RECORDED = _chipbench_path.BENCH / "tests/data/guard_runtrace.jsonl"


def _recorded(monkeypatch):
    rt = trace_mod.RunTrace.from_jsonl(str(RECORDED))
    monkeypatch.setattr(trace_mod, "profiled_traces", lambda: [rt])
    return rt, [{"wall_s": 16.5, "launch_s": 1.47, "launches": 10}]


def test_guard_readers_on_a_recorded_guarded_call(monkeypatch):
    rt, calls = _recorded(monkeypatch)
    spec = Spec(_chipbench_path.ROOT)
    got = {n: spec.reader(n).read(_readings(calls))
           for n in GUARD_READERS + ("splice_ms_per_call",)}
    guard = [e.dur for e in rt.by_kind("guard")]
    reframe = [e.dur for e in rt.by_kind("reframe")]
    assert len(guard) == 3 and len(reframe) == 9
    assert got["guard_ms_per_call"] == pytest.approx(1e3 * sum(guard))
    assert got["reframe_ms_per_call"] == pytest.approx(1e3 * sum(reframe))
    assert got["guard_ms_per_call"] > got["reframe_ms_per_call"] > 0
    # The call has no re-establish: its splice time is the two together.
    assert got["splice_ms_per_call"] == pytest.approx(
        got["guard_ms_per_call"] + got["reframe_ms_per_call"])


def test_guard_readers_count_nested_spans_once(monkeypatch):
    """A guard span inside a reframe span counts in the reframe's time
    only; a guard span inside another guard span in the outer one's."""
    rt = trace_mod.RunTrace(name="cell")
    ev = trace_mod.TraceEvent
    rt.events += [ev(kind="scenario", t=0.0, dur=9.0, id=0),
                  ev(kind="reframe", t=1.0, dur=1.0, id=1, parent=0),
                  ev(kind="guard", t=1.2, dur=0.4, id=2, parent=1),
                  ev(kind="guard", t=3.0, dur=2.0, id=3, parent=0),
                  ev(kind="guard", t=3.5, dur=0.5, id=4, parent=3)]
    monkeypatch.setattr(trace_mod, "profiled_traces", lambda: [rt])
    calls = [{"wall_s": 9.0, "launch_s": 0.0, "launches": 1}]
    spec = Spec(_chipbench_path.ROOT)
    assert spec.reader("guard_ms_per_call").read(_readings(calls)) == \
        pytest.approx(2000.0)
    assert spec.reader("reframe_ms_per_call").read(_readings(calls)) == \
        pytest.approx(1000.0)


@pytest.mark.parametrize("name", GUARD_READERS)
def test_guard_readers_none_untraced_zero_unguarded(name):
    reader = Spec(_chipbench_path.ROOT).reader(name)
    plain = [{"wall_s": 1.0, "launch_s": None, "launches": 1}] * 2
    assert reader.read(_readings(plain)) is None
    calls = [_call(), _call()]
    assert reader.read(_readings(calls)) == 0
