"""The eight ``record_*`` variants of every Pallas lane.

The fused, tiled and sparse kernels share one period body and one output
layout (``repro.kernels.period``): the telemetry flags add outputs and a
measure pass, but never change the state the lane computes.  Each case
runs one lane in one variant on the 8-node testbed fabric, padded to one
lane tile, and pins:

- ψ, ν and the ν record bit-identical to the lane's all-off run;
- the β record and the watermarks, where present, bit-identical to the
  lane's all-on run (a guard band no state reaches never trips);
- the trip column at its "never tripped" sentinel, num_records;
- every :class:`EngineOutputs` field's shape and dtype, which pins the
  positional order the wrappers build and ``split_outputs`` reads.
"""
import functools
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fully_connected, make_links
from repro.kernels import (bittide_fused_pallas, bittide_sparse_pallas,
                           bittide_tiled_fused_pallas, densify, ellify)

OMEGA = 125e6
B, R, EVERY = 8, 2, 4
FLAGS = list(itertools.product((False, True), repeat=3))


@functools.lru_cache(maxsize=None)
def _inputs():
    topo = fully_connected(8)
    e = topo.num_edges
    links = make_links(topo, cable_m=np.where(np.arange(e) % 3, 2.0, 10.0),
                       beta0=(np.arange(e) % 5) - 2.0)
    a, lam, classes, n_pad = densify(topo, links, omega_nom=OMEGA)
    rng = np.random.default_rng(7)
    nu_u = np.zeros((B, n_pad), np.float32)
    nu_u[:, :topo.num_nodes] = rng.uniform(-8, 8, (B, topo.num_nodes)) * 1e-6
    nbr, latf, w = ellify(topo, np.asarray(links.latency_s) * OMEGA)
    return dict(topo=topo, a=a, deg=a.sum(axis=(0, 2)).reshape(1, -1),
                lamsum=lam.sum(axis=(0, 2)), classes=classes,
                nu_u=jnp.asarray(nu_u), nbr=nbr, latf=latf, w=w)


@functools.lru_cache(maxsize=None)
def _run(lane, record_beta, record_watermarks, record_guard):
    x = _inputs()
    nu_u = x["nu_u"]
    psi0 = jnp.zeros_like(nu_u)
    kw = dict(num_records=R, record_every=EVERY, record_beta=record_beta,
              record_watermarks=record_watermarks, record_guard=record_guard,
              interpret=True)
    if record_guard:
        kw.update(guard_lo=-1e9, guard_hi=1e9, guard_stop=R)
    kp = jnp.full((B,), 2e-8, jnp.float32)
    if lane == "sparse":
        return bittide_sparse_pallas(
            psi0, nu_u, nu_u, x["nbr"], x["latf"], x["w"], x["lamsum"], kp,
            0.0, OMEGA * 1e-3, tile_i=128, **kw)
    args = (psi0, nu_u, nu_u, x["a"], x["deg"], x["lamsum"], x["classes"],
            kp, 0.0, OMEGA * 1e-3)
    if lane == "tiled":
        return bittide_tiled_fused_pallas(*args, tile_j=128, **kw)
    return bittide_fused_pallas(*args, **kw)


def _same(got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("flags", FLAGS,
                         ids=["b{:d}w{:d}g{:d}".format(*f) for f in FLAGS])
@pytest.mark.parametrize("lane", ["fused", "tiled", "sparse"])
def test_record_flags_keep_state_and_output_layout(lane, flags):
    record_beta, record_watermarks, record_guard = flags
    out = _run(lane, *flags)
    off = _run(lane, False, False, False)
    on = _run(lane, True, True, True)
    n = _inputs()["nu_u"].shape[1]
    f32, i32 = jnp.float32, jnp.int32

    for field, shape in (("psi", (B, n)), ("nu", (B, n)),
                         ("freq", (R, B, n))):
        got = getattr(out, field)
        assert (got.shape, got.dtype) == (shape, f32), field
        _same(got, getattr(off, field))

    if record_beta:
        assert (out.beta.shape, out.beta.dtype) == ((R, B, n), f32)
        _same(out.beta, on.beta)
    else:
        assert out.beta is None
    if record_watermarks:
        assert [(w.shape, w.dtype) for w in out.watermarks] == [
            ((B, n), f32), ((B, n), i32), ((B, n), f32), ((B, n), f32)]
        for got, want in zip(out.watermarks, on.watermarks):
            _same(got, want)
    else:
        assert out.watermarks is None
    if record_guard:
        assert (out.guard_state.shape, out.guard_state.dtype) == ((B, 1), i32)
        _same(out.guard_state, np.full((B, 1), R))
    else:
        assert out.guard_state is None
