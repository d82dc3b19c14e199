"""The segment plan: a fabric's draw-independent prep, kept across
``run_scenario`` calls (``repro.scenarios.plan``).

1. A repeated call with new draws hits the plan and gives, bit for bit,
   what a call made with no plan gives, on every kernel lane;
   segment-sum makes no plan.
2. A changed fabric — one link's cable (also changed in place, on the
   same object), β0, an event's time — misses, builds, and gives the
   cold call's result.  A changed kp, draw count or telemetry variant
   keeps the stacks, makes the segments' inputs again, and gives the
   cold call's result.
3. One plan is kept: a call on another fabric drops it before it builds;
   each kernel-lane call counts one build or one hit.
4. The key compares by value, to the bit; an input it cannot copy is
   never kept.
5. The bincount λeff fold equals the ``np.add.at`` fold it replaced,
   bit for bit.
"""
import gc
import weakref

import numpy as np
import pytest

from engine_harness import zero_mean_ppm
from repro.core import (ControllerConfig, SimConfig, fully_connected,
                        make_links, torus3d)
from repro.core.frame_model import LinkParams
from repro.kernels import EngineOptions
from repro.kernels.ops import _lamsum_host
from repro.scenarios import LatencyStep, Scenario, edges_between, run_scenario
from repro.scenarios import plan as plan_mod
from repro.scenarios.plan import _clear_plans, matches, snapshot
from repro.telemetry import NULL_TRACE, RunTrace, Telemetry

WM_FIELDS = ("beta_abs_max", "peak_record", "nu_min_ppm", "nu_max_ppm")


@pytest.fixture(autouse=True)
def _cold_plans():
    _clear_plans()


def _fabric(event_t=0.048):
    """FC8 with the §5.6 splice, re-established: two segments, and a
    per-draw λeff fold after the splice."""
    topo = fully_connected(8)
    return dict(
        topo=topo, links=make_links(topo, cable_m=2.0),
        ctrl=ControllerConfig(kp=2e-7),
        scenario=Scenario(events=(LatencyStep(
            t=event_t, edges=edges_between(topo, 0, 2), cable_m=1000.0,
            reestablish=True),)),
        cfg=SimConfig(dt=1e-3, steps=96, record_every=12))


def _draws(b, seed):
    return np.stack([zero_mean_ppm(8, 0.5, seed=seed + s)
                     for s in range(b)]).astype(np.float32)


def _run(fab, ppm, engine="fused", watermarks=True):
    """One traced call; returns the result and its counters."""
    tr = RunTrace()
    res = run_scenario(fab["topo"], fab["links"], fab["ctrl"], ppm,
                       fab["scenario"], fab["cfg"],
                       options=EngineOptions(engine=engine),
                       telemetry=Telemetry(beta=True, watermarks=watermarks,
                                           trace=tr))
    return res, tr.counters


def _plan_counts(counters):
    return {k: v for k, v in counters.items() if k.startswith("prep_plan")}


def _assert_same(a, b):
    for f in ("freq_ppm", "beta", "psi", "nu", "lam", "lam_eff"):
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype and x.shape == y.shape, f
        assert x.tobytes() == y.tobytes(), f
    assert (a.watermarks is None) == (b.watermarks is None)
    if a.watermarks is not None:
        for f in WM_FIELDS:
            np.testing.assert_array_equal(getattr(a.watermarks, f),
                                          getattr(b.watermarks, f))


# -------------------------------------------- 1. a hit is a cold call

@pytest.mark.parametrize("engine", ["fused", "tiled", "per-step", "sparse",
                                    "segment-sum"])
def test_repeated_call_hits_and_matches_a_cold_call(engine):
    fab = _fabric()
    _, first = _run(fab, _draws(5, 0), engine)
    warm, second = _run(fab, _draws(5, 100), engine)
    _clear_plans()
    cold, third = _run(fab, _draws(5, 100), engine)
    kernel = engine != "segment-sum"
    assert _plan_counts(first) == ({"prep_plan_builds": 1} if kernel else {})
    assert _plan_counts(second) == ({"prep_plan_hits": 1} if kernel else {})
    assert _plan_counts(third) == _plan_counts(first)
    assert (plan_mod._PLAN is not None) == kernel
    assert warm.engine == cold.engine == engine
    _assert_same(warm, cold)


# ----------------------------------------- 2. a changed input rebuilds

def _changed(fab, change):
    """``fab`` with one input changed; returns (fabric, draws, watermarks)."""
    links = fab["links"]
    if change == "cable":
        lat = np.array(links.latency_s)
        lat[3] *= 1.5
        return dict(fab, links=LinkParams(lat, links.beta0)), 5, True
    if change == "cable_in_place":
        links.latency_s[3] *= 1.5           # the same object, changed
        return fab, 5, True
    if change == "beta0":
        beta0 = np.array(links.beta0, np.float64)
        beta0[5] += 1.0
        return dict(fab, links=LinkParams(links.latency_s, beta0)), 5, True
    if change == "event_time":
        return _fabric(event_t=0.06), 5, True
    if change == "kp":
        return dict(fab, ctrl=ControllerConfig(kp=3e-7)), 5, True
    if change == "draws":
        return fab, 6, True
    assert change == "telemetry"
    return fab, 5, False


@pytest.mark.parametrize("change", ["cable", "cable_in_place", "beta0",
                                    "event_time", "kp", "draws",
                                    "telemetry"])
def test_changed_input_misses_rebuilds_and_matches_a_cold_call(change):
    fab = _fabric()
    base, _ = _run(fab, _draws(5, 100))
    before = plan_mod._PLAN
    stacks, inputs = before.stacks, dict(before.inputs)
    fab2, b, wm = _changed(fab, change)
    warm, counters = _run(fab2, _draws(b, 100), watermarks=wm)
    after = plan_mod._PLAN
    if change in ("kp", "draws", "telemetry"):
        # The fabric is the same: its stacks stay, the inputs are new.
        assert _plan_counts(counters) == {"prep_plan_hits": 1}
        assert after is before and after.stacks is stacks
    else:
        assert _plan_counts(counters) == {"prep_plan_builds": 1}
        assert after is not before and after.stacks is not stacks
    assert set(after.inputs) == set(inputs)
    assert all(after.inputs[si] is not inputs[si] for si in inputs)
    _clear_plans()
    cold, _ = _run(fab2, _draws(b, 100), watermarks=wm)
    _assert_same(warm, cold)
    if b == 5 and wm:
        # The change reaches the records: a stale plan would show.
        assert base.freq_ppm.tobytes() != warm.freq_ppm.tobytes()


# ------------------------------------------ 3. the bound and the counts

def test_least_recently_used_plan_goes_first(monkeypatch):
    """One plan is kept: a call on another fabric drops the last one
    before it builds its stacks, so two fabrics' stacks never coexist."""
    from repro.scenarios import runner
    fab = _fabric()
    other = _changed(fab, "cable")[0]
    build = runner._build_dense_stacks
    dropped = []

    def watched(*args, **kw):
        gc.collect()
        dropped.append(last() is None if last is not None else None)
        return build(*args, **kw)

    monkeypatch.setattr(runner, "_build_dense_stacks", watched)
    last = None
    seen = []
    for f in (fab, fab, other, fab):
        seen.append(_plan_counts(_run(f, _draws(5, 0))[1]))
        last = weakref.ref(plan_mod._PLAN.stacks)
    assert seen == [{"prep_plan_builds": 1}, {"prep_plan_hits": 1},
                    {"prep_plan_builds": 1}, {"prep_plan_builds": 1}]
    assert dropped == [None, True, True]


def test_each_call_counts_one_build_or_one_hit():
    fab = _fabric()
    seen = [_plan_counts(_run(fab, _draws(5, i))[1]) for i in range(4)]
    assert seen == [{"prep_plan_builds": 1}] + [{"prep_plan_hits": 1}] * 3


# ----------------------------------------------------------- 4. the key

def test_key_compares_by_value_to_the_bit():
    def key(lat, kp, gain_row):
        return (LinkParams(lat, np.zeros(2)), kp,
                ControllerConfig(kp=gain_row), (3, "auto"))

    snap = snapshot(key(np.array([0.0, 1.0]), 0.0, np.array([1.0, 2.0])))
    assert matches(snap, key(np.array([0.0, 1.0]), 0.0, np.array([1.0, 2.0])))
    assert not matches(snap, key(np.array([-0.0, 1.0]), 0.0,
                                 np.array([1.0, 2.0])))
    assert not matches(snap, key(np.array([0.0, 1.0]), -0.0,
                                 np.array([1.0, 2.0])))
    assert not matches(snap, key(np.array([0.0, 1.0], np.float32), 0.0,
                                 np.array([1.0, 2.0])))
    assert not matches(snap, key(np.array([0.0, 1.0]), 0.0,
                                 np.array([1.0, 2.5])))
    assert not matches(snap, key(np.array([0.0, 1.0]), 0,
                                 np.array([1.0, 2.0])))


def test_an_input_it_cannot_copy_is_never_kept():
    key = ("auto", object())
    first = plan_mod.lookup(key, (), NULL_TRACE)
    second = plan_mod.lookup(key, (), NULL_TRACE)
    assert first is not second and plan_mod._PLAN is None
    kept = plan_mod.lookup(("auto",), (object(),), NULL_TRACE)
    kept.inputs[0] = "made"
    again = plan_mod.lookup(("auto",), (object(),), NULL_TRACE)
    assert again is kept and again.inputs == {}


# --------------------------------------------------- 5. the λeff fold

@pytest.mark.parametrize("topo", [fully_connected(8), torus3d(4)],
                         ids=["fc8", "torus3d_4"])
@pytest.mark.parametrize("rows", [1, 5])
def test_bincount_fold_equals_the_add_at_fold(topo, rows):
    rng = np.random.default_rng(rows)
    e, n_pad = topo.num_edges, 128
    lam = rng.normal(0.0, 300.0, (rows, e))
    w = rng.choice([0.0, 0.5, 1.0], e).astype(np.float32)
    ref = np.zeros((rows, n_pad), np.float64)
    np.add.at(ref, (np.broadcast_to(np.arange(rows)[:, None], (rows, e)),
                    np.broadcast_to(np.asarray(topo.dst)[None], (rows, e))),
              lam * np.asarray(w, np.float64))
    got = _lamsum_host(topo, lam, w, rows, n_pad)
    assert got.dtype == np.float32
    assert got.tobytes() == ref.astype(np.float32).tobytes()
