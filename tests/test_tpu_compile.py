"""TPU rehearsal without a chip: every Pallas lane compiles for a v5e
and keeps the TPU pipeline's rules.

The CPU suite runs the kernels in the Pallas interpreter, which checks
their semantics but not whether Mosaic accepts them: VMEM over the scoped
limit, gathers Mosaic has no rule for and misaligned slices only show up
when the kernel is compiled for the chip.  The TPU compiler ships with
jaxlib and compiles for a *described* v5e with none attached, so these
cases compile each lane at the sizes the scenario path runs — the
paper's 8-node testbed, the resident limit, the Fig-18 torus3d(22) — in
every telemetry variant that path dispatches, at the lane and panel
width dispatch picks for it.  Nothing runs; ``chip_smoke.py`` runs the
same lanes on a chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker
imports this file.

The plain interpreter also forgives what the chip does not: the TPU
pipeline writes an output block back whenever its index changes and
never reads it in, so a block revisited later, or skipped by a guard
freeze, flushes a stale buffer over results.  The TPU-semantics
interpreter (``pltpu.InterpretParams``) refuses such revisits and fills
unwritten memory with NaN; each lane runs under it once, with every
telemetry output on and a guard that freezes after the first record.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core import make_links
from repro.kernels import (bittide_fused_pallas, bittide_sparse_pallas,
                           bittide_step_pallas, bittide_tiled_fused_pallas,
                           densify, ellify, select_engine)
from repro.kernels.bittide_step import sparse_panel

F32, I32 = jnp.float32, jnp.int32
B = 8
TORUS_N, TORUS_K = 10752, 6     # torus3d(22): 10,648 nodes padded, degree 6
ALL_TELEMETRY = dict(record_beta=True, record_watermarks=True,
                     record_guard=True)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _compile(fn, sharding, kernel, *shapes):
    """Lower and compile ``fn`` for the described chip; assert the Pallas
    kernel is in the executable under its stable name (the instruction
    name the device trace shows)."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert re.search(rf'%{kernel}(\.\d+)? = [^\n]*custom_call_target='
                     '"tpu_custom_call"', compiled.as_text())
    return compiled


def _batched_state(n):
    """ψ, ν, ν_u (B, N), per-draw gains (B,), mask (1, N), λeff fold."""
    return [((B, n), F32)] * 3 + [((B,), F32)] * 2 + [((1, n), F32),
                                                      ((B, n), F32)]


def _guard(tel):
    if not tel.get("record_guard"):
        return {}
    return dict(guard_lo=jnp.full((B,), -1e3, F32),
                guard_hi=jnp.full((B,), 1e3, F32), guard_stop=3)


def _compile_dense(kernel, one_chip, n, c, tel, **kw):
    def fn(psi, nu, nu_u, kp, boff, mask, lamsum, a, deg, lat):
        return kernel(psi, nu, nu_u, a, deg, lamsum, lat, kp, boff, 125.0,
                      num_records=4, record_every=10, ctrl_mask=mask,
                      interpret=False, **tel, **_guard(tel), **kw)
    name = ("bittide_tiled" if kernel is bittide_tiled_fused_pallas
            else "bittide_fused")
    return _compile(fn, one_chip, name, *_batched_state(n),
                    ((c, n, n), F32), ((1, n), F32), ((B, c), F32))


@pytest.mark.parametrize("tel", [{}, ALL_TELEMETRY],
                         ids=["nu-only", "all-telemetry"])
def test_fused_compiles_at_testbed_size(one_chip, tel):
    """The paper's 8-node FC testbed pads to one (8, 128) tile."""
    assert select_engine(B, 128, 1, **tel) == ("fused", 128)
    _compile_dense(bittide_fused_pallas, one_chip, 128, 1, tel)


def test_fused_pulse_compiles_at_testbed_cell_size(one_chip):
    """FINC/FDEC with integer readout, the testbed.finc_mc kernel: FC8 at
    B = 1,024, one latency class, 56 edges padded to 128, β and
    watermarks, 500 records of 20 periods."""
    from repro.kernels.bittide_step import Incidence
    from repro.kernels.period import Pulses
    b, n, e = 1024, 128, 128
    tel = dict(record_beta=True, record_watermarks=True)
    assert select_engine(b, n, 1, **tel, edges=e, pulses=True) == (
        "fused", n)

    def fn(psi, nu, nu_u, kp, boff, mask, lamsum, a, deg, lat, src, dst,
           scat, lam_e, c0):
        return bittide_fused_pallas(
            psi, nu, nu_u, a, deg, lamsum, lat, kp, boff, 6250.0,
            num_records=500, record_every=20, ctrl_mask=mask,
            interpret=False, **tel, pulses=Pulses(1e-7, 50), c_est=c0,
            edges=Incidence(src, dst, scat), lam_edges=lam_e)
    _compile(fn, one_chip, "bittide_fused",
             *[((b, n), F32)] * 3, ((b,), F32), ((b,), F32), ((1, n), F32),
             ((b, n), F32), ((1, n, n), F32), ((1, n), F32), ((b, 1), F32),
             ((1, e, n), jnp.bfloat16), ((e, n), jnp.bfloat16), ((n, e), F32),
             ((1, e), F32), ((b, n), F32))


def test_fused_compiles_at_resident_limit(one_chip):
    assert select_engine(B, 256, 8, **ALL_TELEMETRY) == ("fused", 256)
    _compile_dense(bittide_fused_pallas, one_chip, 256, 8, ALL_TELEMETRY)


@pytest.mark.parametrize("tel", [{}, ALL_TELEMETRY],
                         ids=["nu-only", "all-telemetry"])
def test_tiled_compiles_at_fig18_torus(one_chip, tel):
    engine, tile_j = select_engine(B, TORUS_N, 1, **tel)
    assert engine == "tiled"
    _compile_dense(bittide_tiled_fused_pallas, one_chip, TORUS_N, 1, tel,
                   tile_j=tile_j)


@pytest.mark.parametrize("tel", [{}, ALL_TELEMETRY],
                         ids=["nu-only", "all-telemetry"])
def test_sparse_compiles_at_fig18_torus(one_chip, tel):
    tile_i = sparse_panel(B, TORUS_N, TORUS_K, **tel)

    def fn(psi, nu, nu_u, kp, boff, mask, lamsum, nbr, latf, w):
        return bittide_sparse_pallas(
            psi, nu, nu_u, nbr, latf, w, lamsum, kp, boff, 125.0,
            num_records=4, record_every=10, tile_i=tile_i, ctrl_mask=mask,
            interpret=False, **tel, **_guard(tel))
    _compile(fn, one_chip, "bittide_sparse", *_batched_state(TORUS_N),
             ((TORUS_K, TORUS_N), I32), ((1, TORUS_K, TORUS_N), F32),
             ((1, TORUS_K, TORUS_N), F32))


def test_per_step_kernel_compiles(one_chip):
    n, c = 256, 2

    def fn(psi, nu, nu_u, a, lam, lat):
        return bittide_step_pallas(psi, nu, nu_u, a, lam, lat, 2e-8, 0.0,
                                   125.0, emit_beta=True, interpret=False)
    _compile(fn, one_chip, "bittide_step", ((n,), F32), ((n,), F32),
             ((n,), F32), ((c, n, n), F32), ((c, n, n), F32), ((c,), F32))


@pytest.mark.parametrize("c,n_pad,e", [(2, 128, 56),
                                       (1, TORUS_N, 10648 * TORUS_K)],
                         ids=["testbed", "fig18-torus"])
def test_stack_builder_holds_one_stack(one_chip, c, n_pad, e):
    """The scenario runner's device stack builder scatters in place: the
    executable holds the (C, N_pad, N_pad) output and no second N²
    buffer (its tile-order transpose is a bitcast)."""
    from repro.scenarios.runner import _scatter_stack
    edges = jax.ShapeDtypeStruct((4, e), I32, sharding=one_chip)
    mem = _scatter_stack.lower(edges, c=c, n_pad=n_pad).compile(
        ).memory_analysis()
    assert mem.output_size_in_bytes == c * n_pad * n_pad * 4
    assert mem.temp_size_in_bytes <= 16 * e


@pytest.mark.parametrize("lane", ["fused", "tiled", "sparse"])
def test_lane_keeps_tpu_pipeline_semantics(lane):
    """Two 128-node panels, three records of two periods, β + watermarks
    + a guard band every draw leaves at record 0: the TPU-semantics
    interpreter must raise no revisit error, produce no NaN where the
    host reads, and agree exactly with the plain interpreter."""
    from engine_harness import bounded_degree_topo
    topo = bounded_degree_topo(200, 2, 3)
    n_pad = 256
    rng = np.random.default_rng(1)
    nu_u = np.zeros((B, n_pad), np.float32)
    nu_u[:, :topo.num_nodes] = rng.uniform(-8e-6, 8e-6,
                                           (B, topo.num_nodes))
    zeros = np.zeros((B, n_pad), np.float32)
    kw = dict(num_records=3, record_every=2, record_guard=True,
              guard_lo=-0.5, guard_hi=0.5, guard_stop=2, **{
                  k: True for k in ALL_TELEMETRY if k != "record_guard"})
    if lane == "sparse":
        tables = ellify(topo, rng.uniform(1.0, 50.0, topo.num_edges))

        def run(interpret):
            return bittide_sparse_pallas(
                zeros, nu_u, nu_u, *tables, zeros[0], 2e-9, 0.0, 125e3,
                tile_i=128, interpret=interpret, **kw)
    else:
        a, _, lat, _ = densify(topo, make_links(topo, cable_m=2.0))
        kernel = (bittide_fused_pallas if lane == "fused"
                  else bittide_tiled_fused_pallas)
        extra = {} if lane == "fused" else {"tile_j": 128}

        def run(interpret):
            return kernel(zeros, nu_u, nu_u, a, a.sum(axis=(0, 2)),
                          zeros[0], lat, 2e-9, 0.0, 125e3,
                          interpret=interpret, **extra, **kw)
    plain, tpu = run(True), run(pltpu.InterpretParams())
    trip = int(np.asarray(tpu.guard_state).min())
    assert trip == 0
    got = [tpu.psi, tpu.nu, tpu.freq[:trip + 1], tpu.beta[:trip + 1],
           *tpu.watermarks, tpu.guard_state]
    want = [plain.psi, plain.nu, plain.freq[:trip + 1],
            plain.beta[:trip + 1], *plain.watermarks, plain.guard_state]
    for x, y in zip(got, want):
        x = np.asarray(x)
        assert not np.isnan(x.astype(np.float64)).any()
        np.testing.assert_array_equal(x, np.asarray(y))
