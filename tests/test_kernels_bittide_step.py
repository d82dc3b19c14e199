"""Pallas kernel vs pure-jnp oracle: shape/topology/param sweeps."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypcompat import given, settings, st

from repro.core import (fully_connected, hourglass, cube, ring, torus3d,
                        random_regular, make_links, simulate, SimConfig,
                        ControllerConfig)
from repro.kernels import (bittide_step, densify, simulate_dense, TILE)
from repro.kernels.ref import bittide_dense_step_ref


def rand_state(npad, seed):
    rng = np.random.default_rng(seed)
    psi = jnp.asarray(rng.normal(0, 50, npad).astype(np.float32))
    nu = jnp.asarray(rng.normal(0, 1e-5, npad).astype(np.float32))
    nu_u = jnp.asarray(rng.uniform(-8e-6, 8e-6, npad).astype(np.float32))
    return psi, nu, nu_u


TOPOS = [
    fully_connected(8),
    hourglass(4),
    cube(),
    ring(5),
    fully_connected(20),        # pads within one tile
    random_regular(130, 3, 0),  # crosses a tile boundary -> 2x2 grid
    torus3d(7),                 # 343 nodes -> 3x3 grid, degree 6
]


@pytest.mark.parametrize("topo", TOPOS, ids=lambda t: t.name)
def test_kernel_matches_ref(topo):
    links = make_links(topo, cable_m=2.0)
    a, lam, lat, npad = densify(topo, links)
    psi, nu, nu_u = rand_state(npad, 0)
    kw = dict(kp=2e-9, beta_off=1.5, dt_frames=125000.0)
    p1, n1 = bittide_step(psi, nu, nu_u, a, lam, lat, **kw)
    p2, n2, _ = bittide_dense_step_ref(psi, nu, nu_u, a, lam, lat, **kw)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-5, atol=1e-11)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5, atol=1e-4)


def test_kernel_multiple_latency_classes():
    """§5.6 setup: one long-fiber link => two latency classes."""
    topo = fully_connected(8)
    cable = np.full(topo.num_edges, 2.0)
    for e in range(topo.num_edges):
        if {int(topo.src[e]), int(topo.dst[e])} == {0, 2}:
            cable[e] = 1000.0
    links = make_links(topo, cable_m=cable)
    a, lam, lat, npad = densify(topo, links)
    assert a.shape[0] == 2  # two classes
    psi, nu, nu_u = rand_state(npad, 1)
    kw = dict(kp=2e-9, beta_off=0.0, dt_frames=125000.0)
    p1, n1 = bittide_step(psi, nu, nu_u, a, lam, lat, **kw)
    p2, n2, _ = bittide_dense_step_ref(psi, nu, nu_u, a, lam, lat, **kw)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-5, atol=1e-11)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16), n=st.integers(4, 40),
       kp=st.floats(1e-10, 1e-7), beta_off=st.floats(-4.0, 4.0))
def test_property_kernel_matches_ref(seed, n, kp, beta_off):
    topo = random_regular(n, 3, seed=seed)
    links = make_links(topo, cable_m=2.0)
    a, lam, lat, npad = densify(topo, links)
    psi, nu, nu_u = rand_state(npad, seed)
    kw = dict(kp=kp, beta_off=beta_off, dt_frames=12500.0)
    p1, n1 = bittide_step(psi, nu, nu_u, a, lam, lat, **kw)
    p2, n2, _ = bittide_dense_step_ref(psi, nu, nu_u, a, lam, lat, **kw)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-4, atol=1e-10)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-4, atol=1e-3)


def test_simulate_dense_matches_core_simulator():
    """Fused-kernel trajectory == reference simulator trajectory."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    rng = np.random.default_rng(7)
    ppm = rng.uniform(-8, 8, 8)
    freq_k, _ = simulate_dense(topo, links, ppm, steps=300, kp=2e-9, dt=1e-3)
    res = simulate(topo, links, ControllerConfig(kp=2e-9),
                   ppm.astype(np.float32),
                   SimConfig(dt=1e-3, steps=300, record_every=1))
    np.testing.assert_allclose(freq_k, res.freq_ppm, rtol=1e-4, atol=1e-4)


def test_simulate_dense_converges():
    topo = cube()
    links = make_links(topo, cable_m=2.0)
    rng = np.random.default_rng(9)
    freq, _ = simulate_dense(topo, links, rng.uniform(-8, 8, 8), steps=400,
                             kp=2e-8, dt=1e-3)
    assert freq[-1].max() - freq[-1].min() < 1.0


def test_padding_nodes_inert():
    """Padded (degree-0) nodes must keep ψ=0, ν=ν_u and not affect others."""
    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    a, lam, lat, npad = densify(topo, links)
    assert npad == TILE
    psi = jnp.zeros((npad,), jnp.float32)
    nu_u = jnp.zeros((npad,), jnp.float32).at[8:].set(5e-6)
    p1, n1 = bittide_step(psi, psi, nu_u, a, lam, lat,
                          kp=2e-9, beta_off=0.0, dt_frames=125000.0)
    # pad nodes see zero occupancy error -> nu = nu_u exactly
    np.testing.assert_allclose(np.asarray(n1[8:]), 5e-6, rtol=1e-6, atol=1e-12)
