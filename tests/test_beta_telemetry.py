"""In-kernel β (buffer occupancy) telemetry: parity, oracles, envelopes.

The dense and sparse Pallas engines record the per-node net occupancy
b_i = Σ_{e→i} w_e·β_e in-kernel at every record point
(``record_beta=True``).  These tests pin the telemetry against three
independent references:

  * the β parity matrix — the in-kernel record equals the segment-sum
    simulator's per-edge β reconstruction (scatter-add by destination)
    to 1e-6 frames on all four engines × {FC8, torus3d(8)}, in the
    converged bounded-occupancy regime the paper operates in
    (``tests/engine_harness.py`` holds the cases + tolerance policy);
  * the exact frame-level oracle — with zero ppm offsets the discrete
    frame simulator's integer occupancies match the in-kernel float
    record EXACTLY (zero tolerance);
  * the closed-form occupancy-envelope oracles of arXiv:2410.05432 —
    FC8 and torus FreqStep / LatencyStep transients recorded in-kernel
    stay inside the analytic exponential bound, the bound is falsifiable
    (a deflated envelope is violated), and a FreqStep's predicted
    equilibrium shift matches the telemetry;

plus the chaining/compile contracts: split runs are bit-identical to
unsplit ones with β on, ``DenseResult.beta_final`` is exact, scenario
replays with β add zero compiles across segments, and the runner's
adjacency stacks, scattered on the device from edge lists, equal
``densify`` cell for cell, dedupe swap-back segments, and compile once.
"""
import numpy as np
import pytest

from engine_harness import (BETA_PARITY_CASES, KERNEL_ENGINES,
                            node_recon as _node_recon,
                            zero_mean_ppm as _zero_mean_ppm)
from repro.core import (ControllerConfig, SimConfig, Topology,
                        fully_connected, make_links, ring, simulate, torus3d)
from repro.core.envelopes import (check_occupancy_envelope, default_slack,
                                  freq_step_envelope, latency_step_envelope)
from repro.core.frame_level import simulate_frames
from repro.kernels import simulate_ensemble_dense, simulate_fused
from repro.kernels.ops import (_fused_engine, _perstep_engine,
                               _sparse_engine)
from repro.scenarios import (FreqStep, LatencyStep, LinkDrop, LinkRestore,
                             Mark, Scenario, edges_between, run_scenario)
from repro.scenarios.runner import _build_dense_stacks
from repro.scenarios.compiler import compile_scenario
from repro.telemetry import Telemetry, no_new_compiles

ENGINES = KERNEL_ENGINES


# ------------------------------------------------------------ parity matrix

@pytest.mark.slow
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize(
    "topo,kp,ppm_scale,steps,rec", BETA_PARITY_CASES,
    ids=[c[0].name for c in BETA_PARITY_CASES])
def test_beta_parity_matrix_vs_segment_sum(topo, kp, ppm_scale, steps, rec,
                                           engine):
    """Acceptance: in-kernel β == segment-sum per-edge reconstruction to
    1e-6 frames at EVERY record point, on every engine × {FC8, torus}."""
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(topo.num_nodes, ppm_scale)
    ref = simulate(topo, links, ControllerConfig(kp=kp), ppm,
                   SimConfig(dt=1e-3, steps=steps, record_every=rec))
    recon = _node_recon(topo, ref.beta)
    res = simulate_fused(topo, links, ppm, steps=steps, kp=kp, dt=1e-3,
                         record_every=rec, engine=engine, record_beta=True)
    assert res.engine == engine
    assert res.beta.shape == (steps // rec, topo.num_nodes)
    np.testing.assert_allclose(res.beta, recon, rtol=0, atol=1e-6)
    # the ν stream must be the usual parity too (β rides along, it does
    # not perturb the trajectory)
    np.testing.assert_allclose(res[0], ref.freq_ppm, rtol=0, atol=1e-6)


def test_beta_rides_along_without_perturbing_nu():
    """record_beta is telemetry only: the ν/ψ trajectory is bit-identical
    with and without it, on every engine."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(8, 2.0)
    for engine in ENGINES:
        kw = dict(steps=60, kp=2e-8, dt=1e-3, record_every=12,
                  engine=engine)
        on = simulate_fused(topo, links, ppm, record_beta=True, **kw)
        off = simulate_fused(topo, links, ppm, **kw)
        np.testing.assert_array_equal(on[0], off[0])
        np.testing.assert_array_equal(on[1], off[1])
        np.testing.assert_array_equal(on.nu, off.nu)
        assert off.beta is None and on.beta is not None


def test_beta_matches_multistep_oracle_batched():
    """Pallas in-kernel β == jnp multistep oracle (use_ref) for a batch,
    including per-draw gains."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0, beta0=1.0)
    B = 8
    ppm = np.stack([_zero_mean_ppm(8, 1.0, seed=s) for s in range(B)])
    kps = np.geomspace(5e-8, 2e-7, B)
    kw = dict(steps=60, dt=1e-3, record_every=12, beta_off=1.0,
              record_beta=True)
    pall = simulate_ensemble_dense(topo, links, ppm, kp=kps, **kw)
    ref = simulate_ensemble_dense(topo, links, ppm, kp=kps, use_ref=True,
                                  **kw)
    assert pall.beta.shape == (B, 5, 8)
    np.testing.assert_allclose(pall.beta, ref.beta, rtol=0, atol=1e-5)


# ------------------------------------------------- exact frame-level oracle

def test_beta_matches_frame_level_oracle_exactly_zero_ppm():
    """Zero ppm offsets + β_off at the setpoint: the in-kernel β equals
    the frame-accurate discrete-event oracle's integer occupancies with
    ZERO tolerance (clocks never move, buffers sit at β0 forever)."""
    topo = fully_connected(4)
    beta0 = 2.0
    links = make_links(topo, cable_m=2.0, beta0=beta0)
    ppm = np.zeros(4, np.float32)

    fl = simulate_frames(topo, links, ppm, duration_s=4e-3,
                         controller=lambda err: 0.0 * err)
    assert not fl.underflow and not fl.overflow
    # The discrete-event oracle samples occupancy at the pop, before the
    # same-tick arrival is delivered, so the count dips exactly one frame
    # below the settled value transiently; the settled (post-delivery)
    # occupancy is the abstract model's β.
    assert np.array_equal(fl.occupancy_max, np.full(topo.num_edges, 18))
    assert fl.occupancy_min.min() >= 17
    # frame-level occupancies are absolute (half-full = depth/2 = 16)
    occ_net = np.zeros(4)
    np.add.at(occ_net, np.asarray(topo.dst), fl.occupancy_max - 16.0)

    for engine in ENGINES:
        res = simulate_fused(topo, links, ppm, steps=40, kp=2e-8,
                             beta_off=beta0, dt=1e-3, record_every=10,
                             engine=engine, record_beta=True)
        # every record identical, and exactly the frame-level net sums
        for t in range(res.beta.shape[0]):
            np.testing.assert_array_equal(res.beta[t], occ_net)


# --------------------------------------------------- closed-form envelopes

def _settle(scale=2.0):
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(8, scale)
    return topo, links, ppm


@pytest.mark.slow
def test_freq_step_stays_inside_closed_form_envelope_fc8():
    """Acceptance: the FC8 FreqStep β transient recorded in-kernel stays
    inside the arXiv:2410.05432 closed-form envelope — and the envelope
    is falsifiable (deflating it 10x breaks it)."""
    topo, links, ppm = _settle()
    kp, dt, rec, steps, t0 = 2e-7, 1e-3, 10, 1200, 0.6
    sc = Scenario(events=(FreqStep(t=t0, nodes=(3,), delta_ppm=2.0),))
    res = run_scenario(topo, links, ControllerConfig(kp=kp), ppm, sc,
                       SimConfig(dt=dt, steps=steps, record_every=rec),
                       engine="fused", record_beta=True)
    env = freq_step_envelope(topo, kp, dt, (3,), 2.0)
    lat_fr = float(np.max(links.latency_s) * 125e6)
    slack = default_slack(env, 1e-5, lat_fr, dt, rec)
    ok, margin = check_occupancy_envelope(res.times, res.beta, t0, env,
                                          slack)
    assert ok, f"transient escaped the closed-form envelope by {-margin}"
    # falsifiability: a 10x-deflated envelope must be violated
    import dataclasses
    tight = dataclasses.replace(env, amp=env.amp / 10.0)
    ok_tight, _ = check_occupancy_envelope(res.times, res.beta, t0, tight,
                                           slack / 10.0)
    assert not ok_tight
    # the equilibrium-shift prediction (mean(δν) − δν)/kp is quantitative
    i0 = np.searchsorted(res.times, t0)
    db_meas = res.beta[-1] - res.beta[i0 - 1]
    np.testing.assert_allclose(db_meas, env.db_inf, rtol=0, atol=0.05)


@pytest.mark.slow
def test_freq_step_envelope_torus():
    """The torus transient obeys the same closed-form bound (λ₂ of the
    3-D torus Laplacian sets the decay)."""
    topo = torus3d(4)
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(topo.num_nodes, 0.5)
    kp, dt, rec, steps, t0 = 5e-7, 1e-3, 10, 1200, 0.6
    sc = Scenario(events=(FreqStep(t=t0, nodes=(0, 9), delta_ppm=1.0),))
    res = run_scenario(topo, links, ControllerConfig(kp=kp), ppm, sc,
                       SimConfig(dt=dt, steps=steps, record_every=rec),
                       engine="auto", record_beta=True)
    env = freq_step_envelope(topo, kp, dt, (0, 9), 1.0)
    assert 0 < env.a_max <= 1
    lat_fr = float(np.max(links.latency_s) * 125e6)
    slack = default_slack(env, 1e-5, lat_fr, dt, rec)
    ok, margin = check_occupancy_envelope(res.times, res.beta, t0, env,
                                          slack)
    assert ok, f"torus transient escaped the envelope by {-margin}"


@pytest.mark.slow
@pytest.mark.parametrize("topo_fn,kp,scale", [
    (lambda: fully_connected(8), 2e-7, 2.0),
    (lambda: torus3d(4), 5e-7, 0.5),
], ids=["fc8", "torus3d4"])
def test_latency_step_stays_inside_closed_form_envelope(topo_fn, kp, scale):
    """Acceptance: a λeff-preserving 2 km cable swap barely moves β — the
    transient stays inside the (tiny) closed-form latency-step envelope,
    the quantitative form of the paper's §5.6 observation."""
    topo = topo_fn()
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(topo.num_nodes, scale)
    dt, rec, steps, t0 = 1e-3, 10, 1200, 0.6
    sw = edges_between(topo, 0, 2 if topo.name.startswith("fully") else 1)
    sc = Scenario(events=(LatencyStep(t=t0, edges=sw, cable_m=1000.0),))
    res = run_scenario(topo, links, ControllerConfig(kp=kp), ppm, sc,
                       SimConfig(dt=dt, steps=steps, record_every=rec),
                       engine="auto", record_beta=True)
    i0 = np.searchsorted(res.times, t0)
    nu_bound = float(np.abs(res.freq_ppm[i0 - 1]).max() * 1e-6) + 1e-7
    dlat = 998.0 / 2.03e8   # 2 m -> 1000 m of fiber, per direction
    env = latency_step_envelope(topo, kp, dt, sw, dlat, nu_bound)
    lat_fr = float(1000.0 / 2.03e8 * 125e6 + 16.0)
    slack = default_slack(env, nu_bound, lat_fr, dt, rec)
    ok, margin = check_occupancy_envelope(res.times, res.beta, t0, env,
                                          slack)
    assert ok, f"swap transient escaped the envelope by {-margin}"
    # and the whole bound is small: the clock network barely notices
    assert env.amp + slack < 0.5


def test_envelope_rejects_unstable_gain():
    """The closed-form bound only covers Δ·kp·λ_max ≤ 1; the oracle must
    refuse gains outside it rather than return a wrong envelope."""
    topo = fully_connected(8)
    with pytest.raises(ValueError, match="outside"):
        freq_step_envelope(topo, 2e-6, 1e-3, (0,), 1.0)


# ------------------------------------------------------ chaining contracts

def test_dense_result_beta_chaining_bit_identical():
    """Satellite fix: DenseResult exposes exact final β — a split run with
    record_beta=True is bit-identical to the unsplit run (records AND
    the .beta_final chaining value)."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0, beta0=1.5)
    ppm = _zero_mean_ppm(8, 2.0)
    kw = dict(kp=2e-8, record_every=12, record_beta=True)
    full = simulate_fused(topo, links, ppm, steps=240, **kw)
    h1 = simulate_fused(topo, links, ppm, steps=120, **kw)
    h2 = simulate_fused(topo, links, ppm, steps=120, init=(h1[1], h1.nu),
                        **kw)
    np.testing.assert_array_equal(
        np.concatenate([h1.beta, h2.beta]), full.beta)
    np.testing.assert_array_equal(h2.beta_final, full.beta_final)
    np.testing.assert_array_equal(full.beta_final, full.beta[-1])


@pytest.mark.parametrize("engine", ENGINES)
def test_scenario_split_beta_bit_identical(engine):
    """A Mark-only (no-event) scenario split on a dense lane reproduces
    the monolithic β stream bit-for-bit — β splices across segment
    boundaries exactly like ψ/ν."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0, beta0=1.0)
    ppm = _zero_mean_ppm(8, 2.0)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    mono = simulate_fused(topo, links, ppm, steps=240, kp=2e-8,
                          record_every=12, engine=engine, record_beta=True)
    res = run_scenario(topo, links, ControllerConfig(kp=2e-8), ppm,
                       Scenario(events=(Mark(t=0.12),)), cfg, engine=engine,
                       record_beta=True)
    assert res.num_launches >= 2
    np.testing.assert_array_equal(res.beta, mono.beta)


def test_scenario_beta_no_recompile_across_segments():
    """Acceptance: a multi-segment scenario with record_beta=True replays
    ONE compiled β-variant kernel — re-running against the warm cache
    adds zero entries on the fused, per-step, and sparse lanes."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(8, 2.0)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    sw = edges_between(topo, 0, 2)
    sc = Scenario(events=(LatencyStep(t=0.12, edges=sw, cable_m=1000.0),))
    for eng, cache in [("fused", _fused_engine),
                       ("per-step", _perstep_engine),
                       ("sparse", _sparse_engine)]:
        run_scenario(topo, links, ControllerConfig(kp=2e-8), ppm, sc, cfg,
                     engine=eng, record_beta=True)   # warm
        size0 = cache._cache_size()
        run_scenario(topo, links, ControllerConfig(kp=2e-8), ppm, sc, cfg,
                     engine=eng, record_beta=True)
        assert cache._cache_size() == size0


@pytest.mark.parametrize("reestablish", [False, True],
                         ids=["lam-preserved", "reestablish"])
def test_scenario_beta_parity_through_latency_step(reestablish):
    """Through a real event (cable swap, with and without buffer
    re-establishment), dense in-kernel β still matches the segment-sum
    reconstruction at every record point — the β stream splices across
    the λeff re-fill exactly like ψ/ν."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(8, 0.5)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    sw = edges_between(topo, 0, 2)
    sc = Scenario(events=(LatencyStep(t=0.12, edges=sw, cable_m=1000.0,
                                      reestablish=reestablish),))
    ctrl = ControllerConfig(kp=2e-7)
    ref = run_scenario(topo, links, ctrl, ppm, sc, cfg)
    recon = _node_recon(topo, ref.beta)
    for eng in ENGINES:
        res = run_scenario(topo, links, ctrl, ppm, sc, cfg, engine=eng,
                           record_beta=True)
        np.testing.assert_allclose(res.beta, recon, rtol=0, atol=1e-6)


# ------------------------------------------- precomputed adjacency stacks

def _assert_stacks_match_densify(topo, links, comp, cfg, stacks):
    from repro.core.frame_model import LinkParams
    from repro.kernels import densify

    assert len(stacks.a) == comp.num_segments
    for seg, a_dev in zip(comp.segments, stacks.a):
        a_ref, _, _, _ = densify(
            topo, LinkParams(latency_s=seg.latency_s,
                             beta0=np.asarray(links.beta0)),
            cfg.omega_nom, lat_classes=comp.lat_classes, edge_w=seg.edge_w)
        np.testing.assert_array_equal(np.asarray(a_dev), np.asarray(a_ref))


def test_dense_stacks_dedupe_and_match_densify():
    """The runner's up-front A stacks equal per-segment densify output
    exactly, and a swap-back scenario reuses the original device buffer
    (one edge-list scatter per unique parameter set)."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    sw = edges_between(topo, 0, 2)
    sc = Scenario(events=(
        LatencyStep(t=0.048, edges=sw, cable_m=1000.0),
        LatencyStep(t=0.096, edges=sw, cable_m=2.0),      # swap back
        LatencyStep(t=0.144, edges=sw, cable_m=1000.0),   # and again
    ))
    comp = compile_scenario(sc, topo, links, cfg)
    stacks = _build_dense_stacks(topo, comp, cfg)
    assert len(stacks.a) == comp.num_segments == 4
    # dedupe: 4 segments, only 2 distinct parameter sets
    assert stacks.num_unique == 2
    assert stacks.a[0] is stacks.a[2]
    assert stacks.a[1] is stacks.a[3]
    _assert_stacks_match_densify(topo, links, comp, cfg, stacks)


def _torus8_drop_swap():
    """torus3d(8): a link drops (weight 0) and comes back (weight 1)
    around a cable swap that is later swapped back."""
    topo = torus3d(8)
    sw, drop = edges_between(topo, 0, 1), edges_between(topo, 9, 10)
    sc = Scenario(events=(
        LinkDrop(t=0.012, edges=drop),
        LatencyStep(t=0.024, edges=sw, cable_m=1000.0),
        LinkRestore(t=0.036, edges=drop),
        LatencyStep(t=0.048, edges=sw, cable_m=2.0),      # swap back
    ))
    # segments: base, dropped, dropped+long, long, base again
    return topo, sc, "tiled", 4, [(0, 4)]


def _ring_parallel_drop():
    """A bounded-degree multigraph: ring(16) with four doubled links,
    one copy of a doubled link dropping (0/1 weights, cells of 2)."""
    r = ring(16)
    topo = Topology(16, src=np.concatenate([r.src, r.src[:4]]),
                    dst=np.concatenate([r.dst, r.dst[:4]]),
                    name="ring16-parallel")
    sc = Scenario(events=(
        LinkDrop(t=0.024, edges=(r.num_edges,)),
        LatencyStep(t=0.036, edges=(2, r.num_edges + 2), cable_m=1000.0),
        LinkRestore(t=0.048, edges=(r.num_edges,)),
    ))
    return topo, sc, "fused", 4, []


@pytest.mark.parametrize("case", [_torus8_drop_swap, _ring_parallel_drop],
                         ids=["torus8-tiled", "ring16-parallel-fused"])
def test_dense_stacks_match_densify_through_drops_and_swaps(case):
    """Every segment's device-built stack equals densify exactly through
    LinkDrop/LinkRestore weights and latency-class moves, on a fabric
    whose cells each hold one edge and on one with parallel edges; the
    run on the named lane matches segment-sum."""
    topo, sc, engine, unique, same = case()
    links = make_links(topo, cable_m=2.0)
    cfg = SimConfig(dt=1e-3, steps=72, record_every=12)
    comp = compile_scenario(sc, topo, links, cfg)
    stacks = _build_dense_stacks(topo, comp, cfg)
    assert stacks.num_unique == unique
    _assert_stacks_match_densify(topo, links, comp, cfg, stacks)
    for i, j in same:
        assert stacks.a[i] is stacks.a[j]
    ctrl, ppm = ControllerConfig(kp=2e-8), _zero_mean_ppm(topo.num_nodes,
                                                          0.5)
    res = run_scenario(topo, links, ctrl, ppm, sc, cfg, compiled=comp,
                       engine=engine)
    ref = run_scenario(topo, links, ctrl, ppm, sc, cfg, compiled=comp)
    assert res.engine == engine
    np.testing.assert_allclose(res.freq_ppm, ref.freq_ppm, rtol=0,
                               atol=1e-6)


@pytest.mark.parametrize("engine", ["fused", "tiled"])
def test_back_to_back_runs_add_no_compile(engine):
    """Two run_scenario calls on one fabric with fresh draws: the second
    compiles nothing — neither the engine nor the device stack builder,
    which compiles once per fabric, not once per call."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    cfg = SimConfig(dt=1e-3, steps=96, record_every=12)
    sc = Scenario(events=(LatencyStep(t=0.048, edges=edges_between(
        topo, 0, 2), cable_m=1000.0, reestablish=True),))
    ctrl = ControllerConfig(kp=2e-8)
    ppm = np.stack([_zero_mean_ppm(8, 2.0, seed=s) for s in range(4)])
    run_scenario(topo, links, ctrl, ppm, sc, cfg, engine=engine,
                 telemetry=Telemetry(beta=True))
    with no_new_compiles():
        res = run_scenario(topo, links, ctrl, ppm[::-1] * 0.5, sc, cfg,
                           engine=engine,
                           telemetry=Telemetry(beta=True, trace=True))
    assert res.engine == engine
    (cs,) = res.trace.by_kind("compile_stats")
    assert cs.data["after"]["dense-stacks"] >= 1
    assert all(v == 0 for v in cs.data["delta"].values())
