"""Engine dispatch: the tile-size heuristic replaces the VMEM cliff.

PR 1 dispatched fused-vs-fallback on a single VMEM-size check, so any
network whose (C, N, N) adjacency outgrew VMEM dropped off the fast path
entirely (scan of per-step kernels).  The tiled engine removes that cliff:
`select_engine` picks a j-panel width instead, and the chosen path is
recorded on the result (`DenseResult.engine` / `SimResult.engine`) so this
file can pin the dispatch, not just the numerics.
"""
import warnings

import numpy as np
import pytest

from repro.core import (ControllerConfig, SimConfig, fully_connected,
                        make_links, simulate, torus3d)
from repro.kernels import (RESIDENT_N_MAX, TILE, TILE_J_MAX, fused_vmem_bytes,
                           select_engine, simulate_ensemble_dense,
                           simulate_fused, sparse_vmem_bytes,
                           tiled_vmem_bytes)
from repro.kernels.bittide_step import VMEM_BUDGET_BYTES


def test_select_engine_regimes():
    """Small nets stay resident, mid/large nets stream panels, and only a
    working set too big for ANY panel width falls back to per-step."""
    assert select_engine(8, 128, 1) == ("fused", 128)
    assert select_engine(8, 256, 2) == ("fused", 256)
    # torus3d(8) pads to 512: beyond the resident cutoff -> tiled.
    engine, tj = select_engine(8, 512, 1)
    assert engine == "tiled" and tj == TILE_J_MAX
    # Fig-18 scale (torus3d(22) pads to 10752): the widest panel that
    # fits, with every telemetry buffer of the variant counted.
    engine, tj = select_engine(8, 10752, 1)
    assert engine == "tiled" and tj == TILE_J_MAX
    assert tiled_vmem_bytes(8, 10752, 1, tj) <= VMEM_BUDGET_BYTES
    tel = dict(record_beta=True, record_watermarks=True, record_guard=True)
    assert select_engine(8, 10752, 1, **tel) == ("tiled", TILE_J_MAX)
    assert (tiled_vmem_bytes(8, 10752, 1, tj, **tel)
            > tiled_vmem_bytes(8, 10752, 1, tj))
    # A giant batch at a class count where no panel fits -> per-step.
    assert select_engine(4096, 10752, 8)[0] == "per-step"


def test_select_engine_sparse_regime_boundaries():
    """The degree-aware fourth regime: explicit N/deg/VMEM-budget cases
    pinning every boundary so future tuning can't silently reroute.

    The sparse branch only activates when the caller supplies the ELL
    slot count ``max_deg``; without it the historical three-regime
    behavior is bit-for-bit unchanged (test_select_engine_regimes)."""
    # A degree bound never reroutes a network a dense lane can hold.
    assert select_engine(8, 128, 1, max_deg=6) == ("fused", 128)
    assert select_engine(8, 256, 2, max_deg=6) == ("fused", 256)
    assert select_engine(8, 512, 1, max_deg=6) == ("tiled", TILE_J_MAX)

    # Mega-scale bounded degree: no (C, N, tj) dense panel fits, but the
    # O(N·K) slot tables + resident O(B·N) state do -> sparse, widest
    # node panel first.  Without the degree bound: per-step fallback.
    assert select_engine(8, 49152, 1) == ("per-step", 0)
    assert select_engine(8, 49152, 1, max_deg=6) == ("sparse", TILE_J_MAX)
    assert sparse_vmem_bytes(8, 49152, 6, TILE_J_MAX) <= VMEM_BUDGET_BYTES

    # Degree pressure narrows the node panel before giving up...
    assert select_engine(8, 49152, 1, max_deg=2048) == ("sparse", TILE)
    assert sparse_vmem_bytes(8, 49152, 2048, TILE) <= VMEM_BUDGET_BYTES
    assert sparse_vmem_bytes(8, 49152, 2048, TILE_J_MAX) > VMEM_BUDGET_BYTES
    # ...and a degree no panel can stream falls through to per-step.
    assert select_engine(8, 49152, 1, max_deg=4096) == ("per-step", 0)

    # The resident state and its node-major gather mirror must fit: past
    # ~50k nodes at B=8 (or under a tighter budget) even degree-6 graphs
    # leave VMEM.
    assert select_engine(8, 57344, 1, max_deg=6) == ("per-step", 0)
    assert select_engine(8, 49152, 1, vmem_budget=8 * 2 ** 20,
                         max_deg=6) == ("per-step", 0)
    # Giant batches stay on per-step regardless of the degree bound.
    assert select_engine(4096, 10752, 8, max_deg=6) == ("per-step", 0)


def test_auto_dispatch_routes_bounded_degree_to_sparse():
    """End-to-end: a 6k-node degree-4 graph with 8 latency classes (the
    (8, 6144, 8) dense working set fits NO panel width) auto-routes to
    the sparse lane and stamps the result metadata."""
    from engine_harness import bounded_degree_topo
    topo = bounded_degree_topo(6000, 4, 0)    # pads to 6144
    rng = np.random.default_rng(5)
    cable = rng.choice(np.linspace(2.0, 200.0, 8), size=topo.num_edges)
    links = make_links(topo, cable_m=cable)
    assert tiled_vmem_bytes(8, 6144, 8, TILE) > VMEM_BUDGET_BYTES
    res = simulate_fused(topo, links, rng.uniform(-8, 8, topo.num_nodes),
                         steps=2, kp=2e-9, record_every=1)
    assert res.engine == "sparse"
    assert res[0].shape == (2, topo.num_nodes)
    assert np.isfinite(res[0]).all()


def test_select_engine_tile_divides_padded_n():
    """The chosen panel width must be a TILE multiple dividing padded N."""
    for n in (128, 384, 512, 1280, 10752):
        engine, tj = select_engine(8, n, 1)
        if engine == "tiled":
            assert tj % TILE == 0 and n % tj == 0
            assert tiled_vmem_bytes(8, n, 1, tj) <= VMEM_BUDGET_BYTES


def test_torus3d8_selects_tiled_path_and_matches_segment_sum():
    """The acceptance bar: torus3d(8) (512 nodes) runs the tiled fused
    engine — NOT the per-step fallback — and matches the segment-sum
    simulator to 1e-6 ppm at every record point."""
    topo = torus3d(8)
    links = make_links(topo, cable_m=2.0)
    ppm = np.random.default_rng(3).uniform(-8, 8, topo.num_nodes)
    steps, rec = 60, 20
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the old path warned on fallback
        res = simulate_fused(topo, links, ppm, steps=steps, kp=2e-9,
                             record_every=rec)
    assert res.engine == "tiled"
    assert res.tile_j == TILE_J_MAX and res.tile_j < 512
    sim = simulate(topo, links, ControllerConfig(kp=2e-9),
                   ppm.astype(np.float32),
                   SimConfig(dt=1e-3, steps=steps, record_every=rec))
    assert res[0].shape == sim.freq_ppm.shape
    np.testing.assert_allclose(res[0], sim.freq_ppm, rtol=0, atol=1e-6)


def test_small_network_stays_on_resident_fused_path():
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = np.random.default_rng(0).uniform(-8, 8, 8)
    res = simulate_fused(topo, links, ppm, steps=20, kp=2e-9, record_every=10)
    assert res.engine == "fused" and res.tile_j == 128
    assert 128 <= RESIDENT_N_MAX
    assert fused_vmem_bytes(8, 128, 1) <= VMEM_BUDGET_BYTES


def test_engine_override_and_metadata_roundtrip():
    """Forced engines are honored and stamped on the result; unpacking
    stays tuple-compatible."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = np.random.default_rng(1).uniform(-8, 8, (3, 8))
    auto = simulate_ensemble_dense(topo, links, ppm, steps=20, kp=2e-9,
                                   record_every=10)
    forced = simulate_ensemble_dense(topo, links, ppm, steps=20, kp=2e-9,
                                     record_every=10, engine="tiled",
                                     tile_j=128)
    ref = simulate_ensemble_dense(topo, links, ppm, steps=20, kp=2e-9,
                                  record_every=10, use_ref=True)
    assert auto.engine == "fused" and forced.engine == "tiled"
    assert ref.engine == "ref"
    freq, psi = forced  # plain 2-tuple unpacking preserved
    np.testing.assert_allclose(freq, auto[0], rtol=0, atol=1e-6)
    with pytest.raises(ValueError, match="unknown engine"):
        simulate_ensemble_dense(topo, links, ppm, steps=20, kp=2e-9,
                                record_every=10, engine="warp")


def test_segment_sum_results_carry_engine_metadata():
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = np.random.default_rng(2).uniform(-8, 8, 8).astype(np.float32)
    res = simulate(topo, links, ControllerConfig(kp=2e-8), ppm,
                   SimConfig(steps=40, record_every=20))
    assert res.engine == "segment-sum"
