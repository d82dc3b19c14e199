"""In-kernel excursion watermarks + flight recorder (PR 8).

The contracts this file pins:

1. Parity matrix — every kernel lane × {FC8, torus3d(8), bounded-degree
   random graph}: the in-kernel watermarks (max |β|, time-of-peak record
   index, ν min/max) equal the reduction of the full ``record_beta``
   record to 1e-6 (exact for the β aggregates: the kernels reuse the
   record-point aggregation bit-for-bit).
2. Watermarks OFF leaves every other output bit-identical (the
   watermark blocks are compile-time-gated, not predicated).
3. Watermarks work WITHOUT a full record — the 1M-node regime.
4. ``Watermarks`` container algebra: from_record / merge re-basing /
   stacking / health report.
5. Flight recorder: run_scenario(trace=...) emits the event taxonomy,
   round-trips JSONL, and introduces ZERO new compiles.
6. compile_stats is the promoted harness guard (same keys, re-exported).
7. check_occupancy_envelope accepts watermarks directly (one-sided
   necessary-condition mode).
"""
import json
import os

import numpy as np
import pytest

from engine_harness import (BETA_PARITY_CASES, KERNEL_ENGINES,
                            bounded_degree_topo, engine_cache_sizes,
                            random_latency_links, zero_mean_ppm)
from repro.core import (ControllerConfig, SimConfig, fully_connected,
                        make_links)
from repro.core.envelopes import (check_occupancy_envelope, default_slack,
                                  freq_step_envelope)
from repro.kernels import simulate_ensemble_dense, simulate_fused
from repro.scenarios import FreqStep, Scenario, run_scenario
from repro.telemetry import (NULL_TRACE, RunTrace, TraceEvent, Watermarks,
                             coerce_trace, compile_stats, no_new_compiles)

FC8_CASE, TORUS_CASE = BETA_PARITY_CASES


def _case_run(case, engine, **kw):
    topo, kp, ppm_scale, steps, rec = case
    links = make_links(topo, cable_m=2.0)
    ppm = zero_mean_ppm(topo.num_nodes, ppm_scale)
    return simulate_fused(topo, links, ppm, steps=steps, kp=kp, dt=1e-3,
                          record_every=rec, engine=engine, **kw)


def _assert_watermark_parity(res):
    """In-kernel watermarks == reduction of the full record."""
    ref = Watermarks.from_record(res.beta, res[0])
    wm = res.watermarks
    np.testing.assert_allclose(wm.beta_abs_max, ref.beta_abs_max,
                               rtol=0, atol=1e-6)
    np.testing.assert_array_equal(wm.peak_record, ref.peak_record)
    np.testing.assert_allclose(wm.nu_min_ppm, ref.nu_min_ppm,
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(wm.nu_max_ppm, ref.nu_max_ppm,
                               rtol=0, atol=1e-6)
    assert wm.num_records == res[0].shape[-2]


# ------------------------------------------------------- 1. parity matrix

@pytest.mark.parametrize("engine", KERNEL_ENGINES)
def test_watermarks_match_record_reduction_fc8(engine):
    res = _case_run(FC8_CASE, engine, record_beta=True,
                    record_watermarks=True)
    _assert_watermark_parity(res)


@pytest.mark.slow
@pytest.mark.parametrize("engine", KERNEL_ENGINES)
def test_watermarks_match_record_reduction_torus(engine):
    res = _case_run(TORUS_CASE, engine, record_beta=True,
                    record_watermarks=True)
    _assert_watermark_parity(res)


@pytest.mark.slow
@pytest.mark.parametrize("engine", KERNEL_ENGINES)
def test_watermarks_match_record_reduction_bounded_degree(engine):
    topo = bounded_degree_topo(24, 4, seed=3)
    links = random_latency_links(topo, seed=7)
    ppm = zero_mean_ppm(topo.num_nodes, 0.5, seed=11)
    res = simulate_fused(topo, links, ppm, steps=120, kp=2e-7, dt=1e-3,
                         record_every=12, engine=engine, record_beta=True,
                         record_watermarks=True)
    _assert_watermark_parity(res)


def test_watermarks_ensemble_batched():
    topo, kp, ppm_scale, steps, rec = FC8_CASE
    links = make_links(topo, cable_m=2.0)
    ppm = np.stack([zero_mean_ppm(topo.num_nodes, ppm_scale, seed=s)
                    for s in (0, 1, 2)])
    res = simulate_ensemble_dense(topo, links, ppm, steps=steps, kp=kp,
                                  dt=1e-3, record_every=rec, engine="fused",
                                  record_beta=True, record_watermarks=True)
    wm = res.watermarks
    assert wm.beta_abs_max.shape == (3, topo.num_nodes)
    ref = Watermarks.from_record(res.beta, res[0])
    np.testing.assert_allclose(wm.beta_abs_max, ref.beta_abs_max,
                               rtol=0, atol=1e-6)
    np.testing.assert_array_equal(wm.peak_record, ref.peak_record)
    # per-draw slicing
    np.testing.assert_array_equal(wm[1].beta_abs_max, wm.beta_abs_max[1])


# --------------------------------------- 2. watermarks-off bit-identical

@pytest.mark.parametrize("engine", KERNEL_ENGINES)
def test_watermarks_do_not_perturb_outputs(engine):
    off = _case_run(FC8_CASE, engine, record_beta=True)
    on = _case_run(FC8_CASE, engine, record_beta=True,
                   record_watermarks=True)
    np.testing.assert_array_equal(off[0], on[0])
    np.testing.assert_array_equal(off[1], on[1])
    np.testing.assert_array_equal(off.nu, on.nu)
    np.testing.assert_array_equal(off.beta, on.beta)
    assert off.watermarks is None and on.watermarks is not None


# ------------------------------------------- 3. watermarks without record

@pytest.mark.parametrize("engine", KERNEL_ENGINES)
def test_watermarks_without_full_record(engine):
    """The 1M-node contract: O(N) watermarks, no (R, N) β record."""
    res = _case_run(FC8_CASE, engine, record_watermarks=True)
    assert res.beta is None
    full = _case_run(FC8_CASE, engine, record_beta=True)
    ref = Watermarks.from_record(full.beta, full[0])
    np.testing.assert_allclose(res.watermarks.beta_abs_max,
                               ref.beta_abs_max, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(res.watermarks.peak_record,
                                  ref.peak_record)


def test_use_ref_oracle_watermarks():
    res = _case_run(FC8_CASE, "auto", use_ref=True, record_watermarks=True)
    full = _case_run(FC8_CASE, "auto", use_ref=True, record_beta=True)
    ref = Watermarks.from_record(full.beta, full[0])
    np.testing.assert_allclose(res.watermarks.beta_abs_max,
                               ref.beta_abs_max, rtol=0, atol=1e-6)
    assert res.beta is None


# --------------------------------------------------- 4. container algebra

def test_merge_rebases_record_indices():
    rng = np.random.default_rng(0)
    beta = rng.normal(size=(10, 6))
    freq = rng.normal(size=(10, 6))
    whole = Watermarks.from_record(beta, freq)
    merged = (Watermarks.from_record(beta[:4], freq[:4])
              .merge(Watermarks.from_record(beta[4:], freq[4:])))
    np.testing.assert_array_equal(merged.beta_abs_max, whole.beta_abs_max)
    np.testing.assert_array_equal(merged.peak_record, whole.peak_record)
    np.testing.assert_array_equal(merged.nu_min_ppm, whole.nu_min_ppm)
    np.testing.assert_array_equal(merged.nu_max_ppm, whole.nu_max_ppm)
    assert merged.num_records == 10


def test_merge_ties_keep_first_occurrence():
    beta = np.array([[2.0], [2.0], [1.0]])
    freq = np.zeros((3, 1))
    a = Watermarks.from_record(beta[:2], freq[:2])
    b = Watermarks.from_record(beta[2:], freq[2:])
    assert int(a.peak_record[0]) == 0          # argmax tie -> first
    assert int(a.merge(b).peak_record[0]) == 0


def test_stack_rejects_mismatched_counts():
    w1 = Watermarks.from_record(np.zeros((4, 2)), np.zeros((4, 2)))
    w2 = Watermarks.from_record(np.zeros((5, 2)), np.zeros((5, 2)))
    with pytest.raises(ValueError):
        Watermarks.stack([w1, w2])


def test_health_report_verdicts():
    wm = Watermarks(beta_abs_max=np.array([3.0, 10.0]),
                    peak_record=np.array([1, 7]),
                    nu_min_ppm=np.array([-2.0, -1.0]),
                    nu_max_ppm=np.array([1.0, 2.0]), num_records=8)
    rep = wm.health_report(depth=32, guard_margin=2.0)
    assert "OK" in rep and "node 1" in rep and "record 7/8" in rep
    assert "OVERFLOW" in wm.health_report(depth=16)


# ---------------------------------------- 5. scenario runner + recorder

def _scenario_setup(steps=144, t0=0.072):
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-7)
    ppm = zero_mean_ppm(topo.num_nodes, 0.5, seed=5)
    scen = Scenario(events=(FreqStep(t=t0, nodes=(2,), delta_ppm=0.02),))
    cfg = SimConfig(dt=1e-3, steps=steps, record_every=12)
    return topo, links, ctrl, ppm, scen, cfg


def _assert_watermark_parity_scn(res):
    ref = Watermarks.from_record(res.beta, res.freq_ppm)
    np.testing.assert_allclose(res.watermarks.beta_abs_max,
                               ref.beta_abs_max, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(res.watermarks.peak_record,
                                  ref.peak_record)


def test_run_scenario_watermarks_all_lanes_agree():
    topo, links, ctrl, ppm, scen, cfg = _scenario_setup()
    wms = {}
    for eng in ("segment-sum", "fused", "sparse"):
        res = run_scenario(topo, links, ctrl, ppm, scen, cfg, engine=eng,
                           record_beta=True, record_watermarks=True)
        if eng != "segment-sum":
            _assert_watermark_parity_scn(res)
        wms[eng] = res.watermarks
    for eng in ("fused", "sparse"):
        np.testing.assert_allclose(wms[eng].beta_abs_max,
                                   wms["segment-sum"].beta_abs_max,
                                   rtol=0, atol=2e-5)
        np.testing.assert_allclose(wms[eng].nu_spread_ppm,
                                   wms["segment-sum"].nu_spread_ppm,
                                   rtol=0, atol=1e-6)


def test_run_scenario_watermarks_chunk_merge_equals_whole():
    """Chunked replay (merge path) == one-chunk run (single launch)."""
    topo, links, ctrl, ppm, scen, cfg = _scenario_setup()
    a = run_scenario(topo, links, ctrl, ppm, scen, cfg, engine="fused",
                     record_watermarks=True, chunk_records=2)
    b = run_scenario(topo, links, ctrl, ppm, scen, cfg, engine="fused",
                     record_watermarks=True, chunk_records=6)
    assert a.num_launches > b.num_launches
    np.testing.assert_array_equal(a.watermarks.beta_abs_max,
                                  b.watermarks.beta_abs_max)
    np.testing.assert_array_equal(a.watermarks.peak_record,
                                  b.watermarks.peak_record)
    assert a.watermarks.num_records == b.watermarks.num_records == 12


def test_trace_taxonomy_and_jsonl_roundtrip(tmp_path):
    topo, links, ctrl, ppm, scen, cfg = _scenario_setup()
    tr = RunTrace(name="unit")
    res = run_scenario(topo, links, ctrl, ppm, scen, cfg, engine="fused",
                       record_watermarks=True, trace=tr)
    assert res.trace is tr
    kinds = {e.kind for e in tr.events}
    assert {"engine_dispatch", "chunk", "compile_stats"} <= kinds
    disp = tr.by_kind("engine_dispatch")[0]
    assert disp.data["engine"] in ("fused", "tiled")
    assert disp.data["vmem_est_bytes"] > 0
    for ch in tr.by_kind("chunk"):
        assert ch.dur is not None and ch.dur >= 0
    # JSONL round-trip
    p = os.fspath(tmp_path / "trace.jsonl")
    tr.to_jsonl(p)
    back = RunTrace.from_jsonl(p)
    assert back.name == "unit" and len(back) == len(tr)
    assert [e.kind for e in back.events] == [e.kind for e in tr.events]
    # schema guard
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"schema": "something-else/9"}\n')
    with pytest.raises(ValueError):
        RunTrace.from_jsonl(os.fspath(bad))
    assert tr.summary().startswith("RunTrace 'unit'")


def test_tracing_adds_zero_new_compiles():
    topo, links, ctrl, ppm, scen, cfg = _scenario_setup()
    # Warm every executable the traced run will need...
    run_scenario(topo, links, ctrl, ppm, scen, cfg, engine="fused",
                 record_watermarks=True)
    # ...then the traced replay must compile NOTHING.
    with no_new_compiles():
        res = run_scenario(topo, links, ctrl, ppm, scen, cfg,
                           engine="fused", record_watermarks=True,
                           trace=True)
    delta = res.trace.by_kind("compile_stats")[0].data["delta"]
    assert all(v == 0 for v in delta.values())


def test_null_trace_and_coercion():
    assert coerce_trace(False) is NULL_TRACE
    assert not NULL_TRACE
    tr = RunTrace()
    assert tr and len(tr) == 0          # empty recorder is still truthy
    assert coerce_trace(tr) is tr
    assert isinstance(coerce_trace(True, name="x"), RunTrace)
    with NULL_TRACE.span("chunk"):
        NULL_TRACE.event("mark")         # all no-ops


def test_trace_event_data_coercion():
    tr = RunTrace()
    tr.event("mark", small=np.arange(3), big=np.zeros((100,)),
             scalar=np.float32(1.5))
    row = json.loads(tr.events[0].to_json())
    assert row["data"]["small"] == [0, 1, 2]
    assert row["data"]["big"] == {"shape": [100], "dtype": "float64"}
    assert row["data"]["scalar"] == 1.5


def test_trace_event_is_frozen():
    ev = TraceEvent(kind="mark", t=0.0)
    with pytest.raises(Exception):
        ev.kind = "other"


# --------------------------------------------- 6. compile_stats promotion

def test_compile_stats_is_the_harness_guard():
    keys = set(compile_stats())
    assert keys == {"fused/tiled", "per-step", "sparse", "segment-sum",
                    "segment-sum-ensemble", "dense-stacks", "read-back"}
    assert engine_cache_sizes is compile_stats
    with pytest.raises(KeyError):
        no_new_compiles(nonsense=1)


# ----------------------------------- 7. envelope check accepts watermarks

@pytest.mark.slow
def test_envelope_check_accepts_watermarks():
    t0 = 0.24
    topo, links, ctrl, ppm, scen, cfg = _scenario_setup(steps=720, t0=t0)
    res = run_scenario(topo, links, ctrl, ppm, scen, cfg, engine="fused",
                       record_beta=True, record_watermarks=True)
    env = freq_step_envelope(topo, float(np.asarray(ctrl.kp)), cfg.dt,
                             nodes=(2,), delta_ppm=0.02)
    nu_bound = (np.abs(ppm).max() + 0.02) * 1e-6
    lat_max = float(np.asarray(links.latency_s).max()) * cfg.omega_nom
    slack = default_slack(env, nu_bound, lat_max, cfg.dt, cfg.record_every)
    ok_full, m_full = check_occupancy_envelope(res.times, res.beta, t0,
                                               env, slack)
    pre = res.beta[res.times < t0][-1]
    ok_wm, m_wm = check_occupancy_envelope(res.times, res.watermarks, t0,
                                           env, slack, b_pre=pre)
    assert ok_full and ok_wm
    # One-sided necessary condition: the watermark margin can only be
    # looser than (or equal to) the full-record margin.
    assert m_wm >= m_full - 1e-9
    with pytest.raises(ValueError):
        check_occupancy_envelope(res.times, res.watermarks, t0, env, slack)
