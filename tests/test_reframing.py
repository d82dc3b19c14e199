"""Closed-loop buffer re-centering: rotation invariants, oracle exactness,
auto-reframe parity, under-depth survival.

The reframing subsystem promotes §4.2's post-sync pointer rotation into a
closed control loop over the whole stack (arXiv:2504.07044's frame
rotation + arXiv:2410.05432's occupancy model).  These tests pin:

  * the frame-rotation invariant — Δλ per edge == applied shift exactly,
    and graph-mode shifts (integer node potentials) have zero cycle sums,
    so every RTT is conserved (hypothesis property over random topologies
    and converged states);
  * exact cross-layer λ bookkeeping at zero ppm — the abstract scenario
    runner, the dense Pallas lanes and the frame-level discrete-event
    oracle agree on λ tables, λ epochs and occupancy jumps with zero
    tolerance;
  * the closed loop — a long DriftRamp + LatencyStep scenario that
    overflows a 32-deep buffer without reframing stays inside it with
    ``auto_reframe`` on FC8 and torus3d(8), on all three Pallas lanes,
    with IDENTICAL splice decisions and shifts across engines, matching
    segment-sum to the engines' float32 parity floor, and compiling each
    engine at most once across all splices;
  * the guard band — a deliberately under-depth buffer survives a
    FreqStep only with ``auto_reframe=True`` (margin defaulted from
    ``envelopes.default_slack`` via ``reframe_guard_margin``).
"""
import numpy as np
import pytest

from hypcompat import given, settings, st

from repro.core import (ControllerConfig, ReframePolicy, SimConfig,
                        fully_connected, make_links, reframe, reframe_net,
                        reframe_state, ring, simulate, torus3d)
from repro.core import frame_level as fl
from repro.core.envelopes import (laplacian, laplacian_pinv,
                                  reframe_guard_margin, reframe_guard_margins)
from repro.core.frame_model import EB_INIT, OMEGA_NOM
from repro.core.reframing import (check_rotation_invariant, graph_shifts,
                                  node_net_occupancy, potential_residual)
from repro.core.topology import cube, hourglass, mesh2d, star
from repro.core.frame_model import _jitted_run
from repro.kernels.ops import _fused_engine, _perstep_engine
from repro.scenarios import (DriftRamp, FreqStep, LatencyStep, Reframe,
                             Scenario, edges_between, run_scenario)
from repro.telemetry import Telemetry

ENGINES = ["fused", "tiled", "per-step"]


def _zero_mean_ppm(n, scale, seed=7):
    ppm = np.random.default_rng(seed).uniform(-scale, scale, n)
    return (ppm - ppm.mean()).astype(np.float32)


def _lam_table(topo, links):
    """(E,) int λ = rint(EB_INIT + λeff + ω·l) — the runner's bookkeeping."""
    return np.rint(EB_INIT + np.asarray(links.beta0, np.float64)
                   + np.asarray(links.latency_s, np.float64) * OMEGA_NOM
                   ).astype(np.int64)


# ------------------------------------------------- rotation invariant (unit)

def test_graph_shifts_recenter_net_and_conserve_cycles():
    topo = fully_connected(8)
    rng = np.random.default_rng(0)
    d = rng.normal(0, 20, 8)
    d -= d.mean()
    x, sh = graph_shifts(topo, d)
    # shifts are literally potential differences -> zero cycle sums
    assert potential_residual(topo, sh) == 0.0
    np.testing.assert_array_equal(sh, x[np.asarray(topo.src)]
                                  - x[np.asarray(topo.dst)])
    # scatter-by-dst recenters the net deviation up to potential rounding
    applied = np.zeros(8)
    np.add.at(applied, np.asarray(topo.dst), sh)
    assert np.abs(d + applied).max() < 0.5 * 7 + 1.0


TOPOS = [fully_connected(8), ring(12), cube(), hourglass(4), star(8),
         mesh2d(3, 4)]


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10**6),
       topo_i=st.integers(0, len(TOPOS) - 1),
       spread=st.floats(2.0, 200.0))
def test_rotation_invariant_property(seed, topo_i, spread):
    """Satellite acceptance: for random converged states, reframe shifts
    satisfy Δλ_edge == shift and ALL cycle sums of λ (RTTs) are preserved
    exactly."""
    topo = TOPOS[topo_i]
    rng = np.random.default_rng(seed)
    links = make_links(topo, cable_m=2.0,
                       beta0=rng.uniform(-4, 4, topo.num_edges))
    # Converged state: uniform ν, arbitrary settled phase offsets.
    psi = rng.normal(0.0, spread, topo.num_nodes)
    nu = np.full(topo.num_nodes, rng.uniform(-1e-5, 1e-5))
    rf = reframe_state(topo, links, psi, nu, mode="graph")
    lam_before = _lam_table(topo, links)
    lam_after = _lam_table(topo, rf.links)
    # Δλ == shift, integer, and zero cycle sums — raises on violation.
    check_rotation_invariant(topo, lam_before, lam_after, rf.shift,
                             graph_mode=True)
    rev = topo.reverse_edge_index()
    np.testing.assert_array_equal(rf.shift + rf.shift[rev], 0)
    np.testing.assert_array_equal(lam_after + lam_after[rev],
                                  lam_before + lam_before[rev])
    # The rotation recenters: a large settled net deviation collapses to
    # the potential-rounding floor.
    if np.abs(rf.net_before).max() > 20.0:
        assert np.abs(rf.net_after).max() < 0.5 * np.abs(rf.net_before).max()


def test_reframe_per_edge_backcompat_and_graph_mode():
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-7)
    cfg = SimConfig(dt=1e-3, steps=600, record_every=20)
    res = simulate(topo, links, ctrl, _zero_mean_ppm(8, 2.0), cfg)
    rf = reframe(res, target=2.0)
    assert rf.mode == "per-edge"
    # per-edge mode recenters every buffer to within half a frame
    assert np.abs(rf.occupancy_after - 2.0).max() <= 0.5
    np.testing.assert_array_equal(rf.shift, np.rint(2.0 - res.beta[-1]))
    rg = reframe(res, target=0.0, mode="graph")
    assert potential_residual(topo, rg.shift) == 0.0
    check_rotation_invariant(topo, _lam_table(topo, links),
                             _lam_table(topo, rg.links), rg.shift,
                             graph_mode=True)
    # net entry point (dense telemetry) computes the same shifts from the
    # same net deviation
    net = node_net_occupancy(topo, res.beta[-1])
    rn = reframe_net(topo, links, net)
    np.testing.assert_array_equal(rn.shift, rg.shift)


def test_reframe_requires_beta_record():
    topo = fully_connected(4)
    links = make_links(topo, cable_m=2.0)
    cfg = SimConfig(dt=1e-3, steps=40, record_every=10, record_beta=False)
    res = simulate(topo, links, ControllerConfig(kp=2e-7),
                   _zero_mean_ppm(4, 1.0), cfg)
    with pytest.raises(ValueError, match="record_beta"):
        reframe(res)


# ------------------------------------------- zero-ppm cross-layer exactness

def test_reframe_zero_ppm_oracle_lambda_bookkeeping_exact():
    """Acceptance: the scenario runner's λ bookkeeping under a Reframe
    equals the frame-level oracle's, exactly, at zero ppm — Δλ == shift,
    occupancy jump == shift, stream spliced with zero loss."""
    topo = ring(3)
    links = make_links(topo, cable_m=2.0)
    ed = edges_between(topo, 0, 1)
    shift = np.array([3, -2])
    ev = Reframe(t=1.0, edges=ed, shift=shift)

    orc = fl.simulate_frames(topo, links, np.zeros(3), 2.5, events=[ev])
    assert orc.lam_constant and not orc.underflow and not orc.overflow
    np.testing.assert_array_equal(orc.rotated[list(ed)], shift)

    # Same rotation in the abstract runner (its own clock: the t=0.12s
    # record boundary) — the λ bookkeeping must agree with the oracle's
    # epochs exactly, before and after.
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    sc = Scenario(events=(Reframe(t=0.12, edges=ed, shift=shift),))
    res = run_scenario(topo, links, ControllerConfig(kp=0.0),
                       np.zeros(3, np.float32), sc, cfg, record_beta=True)
    (rec,) = res.reframes
    assert not rec.auto
    full = np.zeros(topo.num_edges, np.int64)
    full[list(ed)] = shift
    np.testing.assert_array_equal(rec.shift, full)
    np.testing.assert_array_equal(res.lam[1] - res.lam[0], full)
    for e in range(topo.num_edges):
        assert res.lam[0][e] == orc.lam_epochs[e][0]
        assert res.lam[1][e] == orc.lam_epochs[e][-1]
        assert len(orc.lam_epochs[e]) == (2 if e in ed else 1)


def test_reframe_zero_ppm_abstract_beta_jump_exact():
    topo = ring(3)
    links = make_links(topo, cable_m=2.0)
    ed = edges_between(topo, 0, 1)
    shift = np.array([3, -2])
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    sc = Scenario(events=(Reframe(t=0.12, edges=ed, shift=shift),))
    res = run_scenario(topo, links, ControllerConfig(kp=0.0),
                       np.zeros(3, np.float32), sc, cfg, record_beta=True)
    i = np.searchsorted(res.times, 0.12)
    full = np.zeros(topo.num_edges)
    full[list(ed)] = shift
    np.testing.assert_array_equal(res.beta[i + 1] - res.beta[i - 1], full)
    # dense lanes carry the identical rotation in their net telemetry
    for eng in ENGINES:
        d = run_scenario(topo, links, ControllerConfig(kp=0.0),
                         np.zeros(3, np.float32), sc, cfg, engine=eng,
                         record_beta=True)
        np.testing.assert_array_equal(d.lam[1] - d.lam[0],
                                      full.astype(np.int64))
        net_jump = np.zeros(3)
        np.add.at(net_jump, np.asarray(topo.dst)[list(ed)], shift)
        np.testing.assert_array_equal(d.beta[i + 1] - d.beta[i - 1], net_jump)


def test_frame_level_edge_mode_recenters_to_target():
    """Computed (mode="per-edge") rotation in the oracle: off-center buffers
    move exactly to depth/2 + target at zero ppm."""
    topo = ring(3)
    links = make_links(topo, cable_m=2.0)
    r = fl.simulate_frames(topo, links, np.zeros(3), 2.5, init_occ=10,
                           events=[Reframe(t=1.0, mode="per-edge", target=2.0)])
    assert r.lam_constant and not r.underflow and not r.overflow
    np.testing.assert_array_equal(r.rotated, 8)   # 10 -> 18 on every edge
    for e in range(topo.num_edges):
        assert r.lam_epochs[e][-1] - r.lam_epochs[e][0] == 8
    assert r.occupancy_max.max() <= 18


# -------------------------------------------- manual Reframe on the engines

def test_manual_graph_reframe_parity_all_engines():
    """The rotation splice itself costs zero engine parity: a mid-run
    graph-mode Reframe matches segment-sum to <1e-6 ppm on every lane,
    with identical shifts."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-7)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    sc = Scenario(events=(Reframe(t=0.12, mode="graph"),))
    ppm = _zero_mean_ppm(8, 2.0)
    ref = run_scenario(topo, links, ctrl, ppm, sc, cfg, record_beta=True)
    (rec,) = ref.reframes
    assert np.any(rec.shift != 0)        # the rotation actually did work
    np.testing.assert_array_equal(ref.lam[1] - ref.lam[0], rec.shift)
    assert potential_residual(topo, rec.shift) == 0.0
    for eng in ENGINES:
        res = run_scenario(topo, links, ctrl, ppm, sc, cfg, engine=eng,
                           record_beta=True)
        np.testing.assert_allclose(res.freq_ppm, ref.freq_ppm, rtol=0,
                                   atol=1e-6)
        np.testing.assert_array_equal(res.reframes[0].shift, rec.shift)


def test_reframe_event_validation():
    with pytest.raises(ValueError, match="graph-mode"):
        Reframe(t=0.0, edges=(0, 1), mode="graph")
    with pytest.raises(ValueError, match="whole"):
        Reframe(t=0.0, edges=(0,), shift=1.5)
    with pytest.raises(ValueError, match="unknown Reframe mode"):
        Reframe(t=0.0, mode="sideways")


# ------------------------------------------------- the closed loop (slow)

def _fc8_case():
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(8, 1.0)
    ctrl = ControllerConfig(kp=2e-8)
    cfg = SimConfig(dt=1e-3, steps=720, record_every=12)
    sc = Scenario(events=(
        DriftRamp(t=0.06, t_end=0.54, nodes=(0, 1, 2), rate_ppm_per_s=7.5),
        LatencyStep(t=0.6, edges=edges_between(topo, 0, 2), cable_m=1000.0),
    ), name="fc8-drift-swap")
    pol = ReframePolicy(depth=16, margin=4.0)
    return topo, links, ctrl, ppm, sc, cfg, pol, 1e-5


def _torus_case():
    # The post-rotation recovery plateau scales with (record period ×
    # drift rate) — the controller pulls occupancy back toward the drift
    # equilibrium between records — so the torus case records at a finer
    # period to keep the re-centered excursion inside the 32-deep buffer.
    topo = torus3d(8)
    links = make_links(topo, cable_m=2.0)
    ppm = _zero_mean_ppm(topo.num_nodes, 0.25)
    ctrl = ControllerConfig(kp=6e-7)
    cfg = SimConfig(dt=1e-3, steps=384, record_every=6)
    sc = Scenario(events=(
        DriftRamp(t=0.048, t_end=0.24, nodes=tuple(range(64)),
                  rate_ppm_per_s=150.0),
        LatencyStep(t=0.288, edges=edges_between(topo, 0, 1),
                    cable_m=1000.0),
    ), name="torus-drift-swap")
    pol = ReframePolicy(depth=16, margin=5.0)
    return topo, links, ctrl, ppm, sc, cfg, pol, 1e-3


def _late_shift_sum(res, topo):
    """Rotations spliced after the final segment's start (strict: a splice
    exactly on the boundary is already in the lam row)."""
    late = np.zeros(topo.num_edges, np.int64)
    for r in res.reframes:
        if r.record > res.segment_records[-1]:
            late = late + np.asarray(r.shift, np.int64)
    return late


@pytest.mark.slow
@pytest.mark.parametrize("case", [_fc8_case, _torus_case],
                         ids=["fc8", "torus3d8"])
def test_auto_reframe_long_horizon_parity_matrix(case):
    """Acceptance: the auto-reframed DriftRamp+LatencyStep scenario stays
    inside the buffer on every lane.  The kernel lanes share ONE
    in-kernel trip contract — splice records and shifts IDENTICAL to the
    fused reference, trajectories matching to the engines' float32
    parity floor, ``guard_latency == 1`` on every splice — while the
    host-inspected segment-sum lane (per-edge Laplacian-estimate
    trigger, exposure up to one chunk) is checked standalone for the
    same survival and RTT-conservation properties."""
    topo, links, ctrl, ppm, sc, cfg, pol, tol = case()
    hw_half = 32 / 2    # the hardware buffer: 32 deep, 0 = half-full
    rev = topo.reverse_edge_index()
    plain = run_scenario(topo, links, ctrl, ppm, sc, cfg,
                         telemetry=Telemetry(beta=True))
    # Without reframing the per-edge occupancy leaves the 32-deep buffer.
    assert np.abs(plain.beta).max() > hw_half

    # segment-sum, standalone: survival + RTT conservation + λ books.
    seg = run_scenario(topo, links, ctrl, ppm, sc, cfg,
                       telemetry=Telemetry(beta=True, guard=pol))
    assert np.abs(seg.beta).max() < hw_half
    assert len(seg.reframes) >= 3
    total = seg.total_reframe_shift
    np.testing.assert_array_equal(total + total[rev], 0)
    np.testing.assert_array_equal(seg.lam_final,
                                  seg.lam[-1] + _late_shift_sum(seg, topo))

    # Kernel lanes: fused is the reference for the in-kernel contract.
    ref = run_scenario(topo, links, ctrl, ppm, sc, cfg, engine="fused",
                       telemetry=Telemetry(beta=True, guard=pol))
    deg = np.zeros(topo.num_nodes)
    np.add.at(deg, np.asarray(topo.dst), 1.0)
    assert len(ref.reframes) >= 3
    assert all(r.guard_latency == 1 for r in ref.reframes)
    assert np.abs(ref.beta / deg).max() < hw_half
    total = ref.total_reframe_shift
    np.testing.assert_array_equal(total + total[rev], 0)
    np.testing.assert_array_equal(ref.lam_final,
                                  ref.lam[-1] + _late_shift_sum(ref, topo))
    for eng in ["tiled", "per-step"]:
        res = run_scenario(topo, links, ctrl, ppm, sc, cfg, engine=eng,
                           telemetry=Telemetry(beta=True, guard=pol))
        assert res.engine == eng
        np.testing.assert_allclose(res.freq_ppm, ref.freq_ppm, rtol=0,
                                   atol=tol)
        assert len(res.reframes) == len(ref.reframes)
        for a, b in zip(ref.reframes, res.reframes):
            assert a.record == b.record
            assert b.guard_latency == 1
            np.testing.assert_array_equal(a.shift, b.shift)
        # The in-kernel record agrees each lane stayed inside.
        assert np.abs(res.beta / deg).max() < hw_half


@pytest.mark.slow
def test_auto_reframe_zero_recompiles_across_splices():
    """Acceptance: reframe splices rewrite traced λeff inputs only — a
    warm re-run of the whole auto-reframed scenario adds ZERO compile
    entries on every lane."""
    topo, links, ctrl, ppm, sc, cfg, pol, _ = _fc8_case()
    for eng, cache in [("segment-sum", None), ("fused", _fused_engine),
                       ("tiled", _fused_engine),
                       ("per-step", _perstep_engine)]:
        run_scenario(topo, links, ctrl, ppm, sc, cfg, engine=eng,
                     auto_reframe=pol)          # warm
        size0 = (cache._cache_size() if cache is not None
                 else _jitted_run()._cache_size())
        res = run_scenario(topo, links, ctrl, ppm, sc, cfg, engine=eng,
                           auto_reframe=pol)
        size1 = (cache._cache_size() if cache is not None
                 else _jitted_run()._cache_size())
        assert size1 == size0, f"{eng} recompiled across reframe splices"
        assert len(res.reframes) >= 3


def test_auto_reframe_quiet_run_never_trips():
    """A converged, undisturbed scenario never crosses the guard: the
    auto-reframed run is identical to the plain one, with zero splices."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-7)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    sc = Scenario(events=())
    ppm = _zero_mean_ppm(8, 0.5)
    plain = run_scenario(topo, links, ctrl, ppm, sc, cfg, engine="fused",
                         record_beta=True)
    auto = run_scenario(topo, links, ctrl, ppm, sc, cfg, engine="fused",
                        auto_reframe=True)
    assert auto.reframes == []
    np.testing.assert_array_equal(auto.freq_ppm, plain.freq_ppm)
    np.testing.assert_array_equal(auto.beta, plain.beta)


def test_under_depth_buffer_survives_freq_step_only_with_auto_reframe():
    """Acceptance: a deliberately under-depth buffer (depth 12 — smaller
    than the FreqStep's equilibrium occupancy shift) overflows without
    reframing and survives with it.  The margin is sized above the
    post-splice recovery slew (~1.7 frames/record here), per the
    ReframePolicy contract; the envelopes-derived default margin is
    checked for sanity alongside."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-8)
    cfg = SimConfig(dt=1e-3, steps=480, record_every=12)
    sc = Scenario(events=(FreqStep(t=0.12, nodes=(0,), delta_ppm=2.0),))
    ppm = _zero_mean_ppm(8, 0.5)
    depth = 12
    plain = run_scenario(topo, links, ctrl, ppm, sc, cfg, record_beta=True,
                         chunk_records=1)
    # the equilibrium shift alone exceeds the under-depth buffer
    assert np.abs(plain.beta).max() > depth / 2
    pol = ReframePolicy(depth=depth, margin=3.0)
    res = run_scenario(topo, links, ctrl, ppm, sc, cfg, chunk_records=1,
                       auto_reframe=pol)
    assert len(res.reframes) >= 1
    assert np.abs(res.beta).max() < depth / 2
    # the default (margin=None) guard derives from envelopes.default_slack
    # and stays usable for this buffer
    m = reframe_guard_margin(topo, 2e-8, cfg.dt, cfg.record_every,
                             nu_bound=2.5e-6,
                             lat_frames_max=float(
                                 np.max(links.latency_s)) * OMEGA_NOM)
    assert 0 < m < depth / 2


def test_auto_reframe_validation():
    topo = fully_connected(4)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-8)
    cfg = SimConfig(dt=1e-3, steps=120, record_every=12)
    sc = Scenario(events=())
    ppm = _zero_mean_ppm(4, 1.0)
    with pytest.raises(ValueError, match="record_beta"):
        run_scenario(topo, links, ctrl, ppm, sc, cfg, auto_reframe=True,
                     record_beta=False)
    with pytest.raises(ValueError, match="guard band"):
        run_scenario(topo, links, ctrl, ppm, sc, cfg,
                     auto_reframe=ReframePolicy(depth=8, margin=10.0))
    with pytest.raises(ValueError, match="depth"):
        ReframePolicy(depth=0)


def test_auto_reframe_ensemble_per_draw_shifts():
    """Batched runs rotate per draw: shifts are (B, E), the kernel lanes
    share one in-kernel trip decision, and each draw's RTTs are
    conserved; segment-sum's host-side trigger is checked standalone for
    the same per-draw shape and conservation properties."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-8)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    rng = np.random.default_rng(3)
    ppm_b = rng.uniform(-1, 1, (4, 8)).astype(np.float32)
    ppm_b -= ppm_b.mean(axis=1, keepdims=True)
    sc = Scenario(events=(DriftRamp(t=0.06, t_end=0.18, nodes=(0, 1),
                                    rate_ppm_per_s=20.0),))
    pol = ReframePolicy(depth=16, margin=4.0)
    rev = topo.reverse_edge_index()
    fus = run_scenario(topo, links, ctrl, ppm_b, sc, cfg, engine="fused",
                       telemetry=Telemetry(guard=pol))
    til = run_scenario(topo, links, ctrl, ppm_b, sc, cfg, engine="tiled",
                       telemetry=Telemetry(guard=pol))
    assert len(fus.reframes) >= 1
    assert fus.reframes[0].shift.shape == (4, topo.num_edges)
    assert len(til.reframes) == len(fus.reframes)
    for a, b in zip(fus.reframes, til.reframes):
        assert a.record == b.record
        assert a.guard_latency == b.guard_latency == 1
        np.testing.assert_array_equal(a.shift, b.shift)
    total = fus.total_reframe_shift
    np.testing.assert_array_equal(total + total[..., rev], 0)
    np.testing.assert_allclose(til.freq_ppm, fus.freq_ppm, rtol=0,
                               atol=1e-5)
    seg = run_scenario(topo, links, ctrl, ppm_b, sc, cfg,
                       telemetry=Telemetry(guard=pol))
    assert len(seg.reframes) >= 1
    assert seg.reframes[0].shift.shape == (4, topo.num_edges)
    assert all(r.guard_latency >= 1 for r in seg.reframes)
    total = seg.total_reframe_shift
    np.testing.assert_array_equal(total + total[..., rev], 0)


def test_guard_lane_kernel_parity_matrix():
    """Harness guard-on lane: the in-kernel trip record index, the
    spliced shifts, and the one-record guard latency are IDENTICAL
    across all four kernel engines (same degree-scaled band over the
    same in-kernel β measurement)."""
    from engine_harness import KERNEL_ENGINES, guard_case, run_guarded
    topo, links, ctrl, ppm, sc, cfg, pol = guard_case()
    ref = None
    for eng in KERNEL_ENGINES:
        res = run_guarded(topo, links, ctrl, ppm, sc, cfg, eng, pol)
        assert len(res.reframes) >= 1, eng
        assert all(r.guard_latency == 1 for r in res.reframes), eng
        recs = [(r.record, np.asarray(r.shift).tolist())
                for r in res.reframes]
        if ref is None:
            ref = recs
        else:
            assert recs == ref, f"{eng} trip decisions diverge from fused"


def test_guard_lane_never_trips_bit_identical():
    """Harness guard-on lane: the guard-variant executables are
    observation-free — when the band is never crossed, every kernel
    lane's trajectory is BIT-identical to its guard-off run and no
    splice is logged."""
    from engine_harness import KERNEL_ENGINES, run_guarded
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=2e-7)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    sc = Scenario(events=())
    ppm = _zero_mean_ppm(8, 0.5)
    pol = ReframePolicy(depth=64, margin=1.0)   # band far outside reach
    for eng in KERNEL_ENGINES:
        off = run_guarded(topo, links, ctrl, ppm, sc, cfg, eng, None)
        on = run_guarded(topo, links, ctrl, ppm, sc, cfg, eng, pol)
        assert on.reframes == []
        np.testing.assert_array_equal(on.freq_ppm, off.freq_ppm, err_msg=eng)
        np.testing.assert_array_equal(on.beta, off.beta, err_msg=eng)
        np.testing.assert_array_equal(on.psi, off.psi, err_msg=eng)
        np.testing.assert_array_equal(on.nu, off.nu, err_msg=eng)


@pytest.mark.slow
def test_guard_lane_spliced_resume_no_new_compiles():
    """Harness guard-on lane: a warm re-run of a guard-tripping scenario
    adds ZERO compile entries on every kernel lane — the in-kernel trip,
    the partial-chunk resume (traced stop cap), and the λeff rotation
    all reuse one executable per lane."""
    from engine_harness import (KERNEL_ENGINES, guard_case, no_new_compiles,
                                run_guarded)
    topo, links, ctrl, ppm, sc, cfg, pol = guard_case()
    for eng in KERNEL_ENGINES:
        run_guarded(topo, links, ctrl, ppm, sc, cfg, eng, pol)    # warm
        with no_new_compiles():
            res = run_guarded(topo, links, ctrl, ppm, sc, cfg, eng, pol)
        assert len(res.reframes) >= 1, eng


def test_auto_reframe_per_draw_guard_margins():
    """Satellite regression (two-draw two-gain): with ``margin=None``
    each draw's default margin derives from its OWN gain and disturbance
    bound via :func:`reframe_guard_margins` — the pre-redesign runner
    computed ONE margin from the batch-max gain and batch-max
    disturbance, over-guarding quiet draws.  The batched helper must
    match the scalar one element-wise and actually differ across draws
    whose bounds differ; the runner must thread per-draw gains AND
    per-draw disturbance magnitudes through the guard end to end."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    cfg = SimConfig(dt=1e-3, steps=240, record_every=12)
    lat_big = 2000.0    # frames — enough ν·ω·l coupling to leave the
    #                     1-frame floor and expose the per-draw term
    m = reframe_guard_margins(topo, [2e-8, 2e-7], cfg.dt, cfg.record_every,
                              [5e-5, 2e-4], lat_big)
    assert m.shape == (2,)
    for i, (kp, nu) in enumerate([(2e-8, 5e-5), (2e-7, 2e-4)]):
        assert m[i] == reframe_guard_margin(topo, kp, cfg.dt,
                                            cfg.record_every, nu, lat_big)
    assert m[0] != m[1]
    # End to end: two draws, two gains, per-draw FreqStep magnitudes,
    # margin=None — the fused lane's in-kernel guard rotates ONLY the
    # drifting draw (the quiet draw logs zero shift rows bit-exactly).
    ctrl = ControllerConfig(kp=np.array([2e-8, 3e-8]))
    ppm_b = np.tile(_zero_mean_ppm(8, 0.5), (2, 1))
    sc = Scenario(events=(FreqStep(t=0.06, nodes=(0,),
                                   delta_ppm=np.array([0.0, 8.0])),))
    pol = ReframePolicy(depth=12, margin=None)
    res = run_scenario(topo, links, ctrl, ppm_b, sc, cfg, engine="fused",
                       telemetry=Telemetry(beta=True, guard=pol))
    assert len(res.reframes) >= 1
    for r in res.reframes:
        assert r.guard_latency == 1
        np.testing.assert_array_equal(r.shift[0], 0)
    assert max(np.abs(r.shift[1]).max() for r in res.reframes) > 0


def _isolate_node_3(topo):
    """Weights that cut node 3 out of fully_connected(4) in both
    directions: symmetric, but the graph falls into two parts."""
    w = np.ones(topo.num_edges)
    w[(np.asarray(topo.src) == 3) | (np.asarray(topo.dst) == 3)] = 0.0
    return w


@pytest.mark.parametrize("case", ["connected", "partitioned", "asymmetric"])
def test_laplacian_pinv_matches_svd_pinv(case):
    """The LU form (L + J/n)⁻¹ − J/n of a connected symmetric Laplacian is
    its pseudo-inverse; a partitioned graph or direction-asymmetric
    weights fall back to the SVD.  Either way the result is
    ``np.linalg.pinv`` to float64 rounding."""
    from engine_harness import bounded_degree_topo
    from repro.core import fully_connected, torus3d
    if case == "connected":
        lap = laplacian(torus3d(4))
    elif case == "partitioned":
        topo = fully_connected(4)
        lap = laplacian(topo, _isolate_node_3(topo))
    else:
        lap = laplacian(bounded_degree_topo(24, 3, 1))
        assert not np.array_equal(lap, lap.T)
    np.testing.assert_allclose(laplacian_pinv(lap), np.linalg.pinv(lap),
                               rtol=0, atol=1e-9)
