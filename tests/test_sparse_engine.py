"""Sparse ELL engine: table construction, random-graph parity, panels.

The sparse lane's correctness burden is different from the dense lanes':
it must agree with the segment-sum simulator on *arbitrary* bounded-
degree graphs (ragged in-degrees, isolated nodes, degree-1 leaves,
always-padded slots), not just the paper's regular topologies — the ELL
slot assignment, padding-slot self-indexing, and per-panel staging are
all new failure surfaces.  The hypothesis property test (via
``hypcompat`` — scalar strategies only, so the deterministic fallback
replays the same graphs) draws random bounded-degree digraphs × random
latency classes and pins sparse == segment-sum at every record point;
the unit tests pin the table layout itself, bit-exactness of padded
slots and multi-panel streaming, and the lane's error contracts.
"""
import numpy as np
import pytest
from hypcompat import example, given, settings, st

from engine_harness import (BETA_ATOL_CROSS_FRAMES, BETA_RTOL_CROSS,
                            FREQ_ATOL_PPM,
                            bounded_degree_topo, node_recon, parity_ppm,
                            random_latency_links)
from repro.core import (ControllerConfig, SimConfig, fully_connected,
                        make_links, random_regular, simulate)
from repro.kernels import (bittide_sparse_pallas, ellify, max_in_degree,
                           simulate_ensemble_dense, simulate_fused)

OMEGA = 125e6


# ------------------------------------------------------------ ellify layout

def test_ellify_roundtrips_every_edge():
    """Each real edge lands in exactly one slot carrying its own latency
    and weight; padding slots self-index with weight 0; per-node slot
    degree equals the in-degree (multigraph edges NOT merged)."""
    topo = bounded_degree_topo(24, 4, 1, isolated=2, leaves=2)
    lat = np.arange(topo.num_edges, dtype=np.float64) + 1.0
    nbr, latf, w = ellify(topo, lat)
    k = max_in_degree(topo)
    n_pad = 128
    assert nbr.shape == (k, n_pad)
    assert latf.shape == (1, k, n_pad) and w.shape == (1, k, n_pad)

    nbr_np = np.asarray(nbr)
    latf_np = np.asarray(latf[0])
    w_np = np.asarray(w[0])
    live = w_np == 1.0
    got = sorted(zip(nbr_np[live].tolist(),
                     np.nonzero(live)[1].tolist(),
                     latf_np[live].tolist()))
    ref = sorted(zip(np.asarray(topo.src).tolist(),
                     np.asarray(topo.dst).tolist(), lat.tolist()))
    assert got == ref
    # padding slots: valid self-gather address, zero contribution
    pad = ~live
    np.testing.assert_array_equal(nbr_np[pad], np.nonzero(pad)[1])
    np.testing.assert_array_equal(latf_np[pad], 0.0)
    deg = w_np.sum(axis=0)
    np.testing.assert_array_equal(deg[:topo.num_nodes], topo.in_degree)
    np.testing.assert_array_equal(deg[topo.num_nodes:], 0.0)


def test_ellify_per_draw_tables_and_errors():
    topo = fully_connected(4)
    e = topo.num_edges
    lat_b = np.tile(np.arange(e, dtype=np.float64), (3, 1))
    w_b = np.ones((3, e))
    w_b[1, 0] = 0.0
    nbr, latf, w = ellify(topo, lat_b, edge_w=w_b)
    assert latf.shape[0] == 3 and w.shape[0] == 3
    assert float(np.asarray(w[1]).sum()) == e - 1

    with pytest.raises(ValueError, match="lat_frames"):
        ellify(topo, np.zeros(e + 1))
    with pytest.raises(ValueError, match="edge_w"):
        ellify(topo, np.zeros(e), edge_w=np.zeros(e - 1))
    with pytest.raises(ValueError, match="max_deg"):
        ellify(topo, np.zeros(e), max_deg=max_in_degree(topo) - 1)


# ---------------------------------------------------- kernel bit-exactness

def _kernel_inputs(topo, seed=0, b=8):
    n_pad = ((topo.num_nodes + 127) // 128) * 128
    rng = np.random.default_rng(seed)
    nu_u = np.zeros((b, n_pad), np.float32)
    nu_u[:, :topo.num_nodes] = rng.uniform(-8e-6, 8e-6,
                                           (b, topo.num_nodes))
    psi = np.zeros((b, n_pad), np.float32)
    lat_f = rng.uniform(1e3, 5e4, topo.num_edges)
    return psi, nu_u, lat_f, n_pad


def _run_kernel(topo, psi, nu_u, nbr, latf, w, **kw):
    base = dict(num_records=4, record_every=3, record_beta=True,
                interpret=True)
    base.update(kw)
    return bittide_sparse_pallas(
        psi, psi, nu_u, nbr, latf, w, np.zeros(psi.shape[1], np.float32),
        2e-9, 0.0, 125e3, **base)


def test_extra_padded_slots_are_bit_exact():
    """max-degree padding: tables with K = max_deg + 2 always-padded
    slots produce BIT-identical trajectories (padding gathers a valid
    address and adds exactly 0.0f)."""
    topo = bounded_degree_topo(32, 3, 2)
    psi, nu_u, lat_f, _ = _kernel_inputs(topo)
    tight = ellify(topo, lat_f)
    loose = ellify(topo, lat_f, max_deg=max_in_degree(topo) + 2)
    a = _run_kernel(topo, psi, nu_u, *tight)
    b = _run_kernel(topo, psi, nu_u, *loose)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_multi_panel_streaming_bit_exact():
    """Multi-panel table streaming (tile_i < N, staged updates + commit)
    is bit-identical to the single-panel fast path."""
    topo = random_regular(300, 3, 0)           # n_pad = 384 -> 3 panels
    psi, nu_u, lat_f, n_pad = _kernel_inputs(topo, seed=4)
    tabs = ellify(topo, lat_f)
    single = _run_kernel(topo, psi, nu_u, *tabs, tile_i=n_pad)
    multi = _run_kernel(topo, psi, nu_u, *tabs, tile_i=128)
    for x, y in zip(single, multi):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_kernel_shape_and_tile_errors():
    topo = fully_connected(8)
    psi, nu_u, lat_f, n_pad = _kernel_inputs(topo)
    nbr, latf, w = ellify(topo, lat_f)
    with pytest.raises(ValueError, match="nbr"):
        _run_kernel(topo, psi, nu_u, nbr[:, :64], latf, w)
    with pytest.raises(ValueError, match="latf"):
        _run_kernel(topo, psi, nu_u, nbr, latf[0], w)
    with pytest.raises(ValueError, match="tile_i"):
        _run_kernel(topo, psi, nu_u, nbr, latf, w, tile_i=64)


# ------------------------------------------------ random-graph parity (hyp)

@settings(max_examples=8, deadline=None)
@given(n=st.integers(12, 40), max_deg=st.integers(1, 5),
       gseed=st.integers(0, 2 ** 16), lseed=st.integers(0, 2 ** 16),
       heterogeneous=st.booleans())
# β near 273 frames differs from segment-sum by 0.75 float32 ulp here.
@example(n=12, max_deg=4, gseed=0, lseed=101, heterogeneous=False)
def test_sparse_matches_segment_sum_on_random_graphs(n, max_deg, gseed,
                                                     lseed, heterogeneous):
    """Satellite property: on random bounded-degree digraphs × random
    latency draws (few-class and fully heterogeneous), the sparse lane
    matches the segment-sum simulator at EVERY record point — ν to the
    1e-6-ppm parity bar and β to the cross-engine float32 floor.  Every
    graph contains an isolated node (zero in-degree), a degree-1 leaf,
    and a node at max_deg, so the padding edge cases ride every example.
    """
    topo = bounded_degree_topo(max(n, max_deg + 4), max_deg, gseed,
                               isolated=1, leaves=1)
    links = random_latency_links(topo, lseed, heterogeneous=heterogeneous)
    ppm = parity_ppm(topo, seed=gseed % 97)
    kp, steps, rec = 2e-9, 48, 12
    ref = simulate(topo, links, ControllerConfig(kp=kp), ppm,
                   SimConfig(dt=1e-3, steps=steps, record_every=rec,
                             record_beta=True))
    res = simulate_fused(topo, links, ppm, steps=steps, kp=kp, dt=1e-3,
                         record_every=rec, engine="sparse",
                         record_beta=True)
    assert res.engine == "sparse"
    np.testing.assert_allclose(res[0], ref.freq_ppm, rtol=0,
                               atol=FREQ_ATOL_PPM)
    np.testing.assert_allclose(res.beta, node_recon(topo, ref.beta),
                               rtol=BETA_RTOL_CROSS,
                               atol=BETA_ATOL_CROSS_FRAMES)


def test_isolated_nodes_hold_their_oscillator():
    """Zero in-degree ⇒ the controller error is identically 0: an
    isolated node's recorded frequency IS its unadjusted oscillator at
    every record point (and matches segment-sum exactly like the rest)."""
    topo = bounded_degree_topo(16, 3, 0, isolated=2, leaves=2)
    links = make_links(topo, cable_m=2.0)
    ppm = parity_ppm(topo, seed=3)
    ref = simulate(topo, links, ControllerConfig(kp=2e-9), ppm,
                   SimConfig(dt=1e-3, steps=48, record_every=12))
    res = simulate_fused(topo, links, ppm, steps=48, kp=2e-9, dt=1e-3,
                         record_every=12, engine="sparse")
    np.testing.assert_allclose(res[0], ref.freq_ppm, rtol=0,
                               atol=FREQ_ATOL_PPM)
    np.testing.assert_allclose(res[0][:, -2:],
                               np.broadcast_to(ppm[-2:], (4, 2)),
                               rtol=0, atol=1e-5)


# ------------------------------------------------------ per-draw edge data

def test_per_draw_edge_weights_match_per_draw_singles():
    """A (B, E) edge_w batch (each draw dropping a different link) on the
    sparse lane equals B single runs each with that draw's (E,) weights."""
    topo = fully_connected(6)
    links = make_links(topo, cable_m=2.0)
    b, e = 4, topo.num_edges
    ppm = np.stack([parity_ppm(topo, seed=s) for s in range(b)])
    w_b = np.ones((b, e))
    for d in range(b):
        w_b[d, d * 3] = 0.0
    kw = dict(steps=48, kp=2e-9, dt=1e-3, record_every=12,
              record_beta=True)
    batch = simulate_ensemble_dense(topo, links, ppm, engine="sparse",
                                    edge_w=w_b, **kw)
    assert batch.engine == "sparse"
    for d in range(b):
        single = simulate_ensemble_dense(topo, links, ppm[d][None],
                                         engine="sparse", edge_w=w_b[d],
                                         **kw)
        np.testing.assert_allclose(batch[0][d], single[0][0], rtol=0,
                                   atol=FREQ_ATOL_PPM)
        np.testing.assert_allclose(batch.beta[d], single.beta[0], rtol=0,
                                   atol=BETA_ATOL_CROSS_FRAMES)


def test_sparse_lane_error_contracts():
    """use_ref has no sparse oracle; per-draw edge_w on a dense lane
    keeps the clear segment-sum/sparse redirect."""
    topo = fully_connected(4)
    links = make_links(topo, cable_m=2.0)
    ppm = np.zeros((2, 4), np.float32)
    w_b = np.ones((2, topo.num_edges))
    with pytest.raises(ValueError, match="use_ref"):
        simulate_ensemble_dense(topo, links, ppm, steps=12, kp=2e-9,
                                engine="sparse", use_ref=True)
    with pytest.raises(ValueError, match="segment-sum"):
        simulate_ensemble_dense(topo, links, ppm, steps=12, kp=2e-9,
                                engine="fused", edge_w=w_b)
