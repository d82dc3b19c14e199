"""The paper's FC8 testbed under its FINC/FDEC controller with integer
buffer readout, on the fused Pallas lane (arXiv:2503.05033 §4.3, §5.7).

``run_scenario`` dispatches a ``ControllerConfig(kind="discrete")`` run
with ``SimConfig(quantize_beta=True)`` to the fused lane, which forms
every edge's occupancy each period, rounds it to a whole frame, sums it
by destination and takes the pulse decisions in-kernel, carrying
``c_est`` across periods, chunks and segments.  These cases hold it, in
the Pallas interpreter, to the segment-sum engine
(``repro.core.frame_model``) bit for bit: the ν record, the final ψ, ν
and ``c_est``, and the frequency watermarks; plain, across a §5.6
splice re-established mid-run, and with one node in holdover.  They pin
the dispatch, the other lanes' refusals, the ``engine_dispatch`` fields
and transfer counters, and that the proportional lanes' kernels are the
ones they were.
"""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ControllerConfig, SimConfig, fully_connected,
                        make_links, ring)
from repro.kernels import (EngineOptions, bittide_fused_pallas,
                           bittide_tiled_fused_pallas, densify)
from repro.kernels.bittide_step import select_engine
from repro.scenarios import (LatencyStep, NodeHoldover, NodeReset, Scenario,
                             edges_between, run_scenario)
from repro.scenarios import plan as plans
from repro.telemetry import RunTrace, Telemetry

TOPO = fully_connected(8)
LINKS = make_links(TOPO, cable_m=2.0)
B = 16
PPM = np.random.default_rng(20).uniform(-8, 8, (B, 8)).astype(np.float32)
TEL = Telemetry(beta=True, watermarks=True)


def _ctrl(fs):
    return ControllerConfig(kind="discrete", kp=2e-8, fs=fs,
                            pulses_per_update=50)


def _cfg(periods=2000):
    return SimConfig(dt=5e-5, steps=periods, record_every=20,
                     quantize_beta=True)


def _run(engine, ctrl, scenario=Scenario(events=()), cfg=None, ppm=PPM,
         telemetry=TEL, chunk=None):
    return run_scenario(TOPO, LINKS, ctrl, ppm, scenario, cfg or _cfg(),
                        options=EngineOptions(engine=engine,
                                              chunk_records=chunk),
                        telemetry=telemetry)


def _same_run(fused, ref):
    assert fused.engine == "fused"
    np.testing.assert_array_equal(fused.freq_ppm, ref.freq_ppm)
    np.testing.assert_array_equal(fused.psi, ref.psi)
    np.testing.assert_array_equal(fused.nu, ref.nu)
    np.testing.assert_array_equal(fused.c_state["c_est"],
                                  ref.c_state["c_est"])
    np.testing.assert_array_equal(fused.watermarks.nu_min_ppm,
                                  ref.watermarks.nu_min_ppm)
    np.testing.assert_array_equal(fused.watermarks.nu_max_ppm,
                                  ref.watermarks.nu_max_ppm)


@pytest.mark.parametrize("fs", [1e-8, 1e-7], ids=["fs1e-8", "fs1e-7"])
def test_fused_matches_segment_sum_bit_for_bit(fs):
    """2,000 periods, two launches a call: the fused lane's ν record and
    final c_est are segment-sum's, and its pulses moved the clocks."""
    fused = _run("auto", _ctrl(fs), chunk=50)
    ref = _run("segment-sum", _ctrl(fs), chunk=50)
    _same_run(fused, ref)
    assert fused.num_launches == 2
    pulses = np.rint(fused.c_state["c_est"] / fs)
    assert np.abs(pulses).max() >= 10
    np.testing.assert_allclose(fused.c_state["c_est"], pulses * fs,
                               rtol=0, atol=fs / 8)


def test_fused_matches_segment_sum_across_a_splice():
    """A §5.6 fibre splice of link (0, 2) to 1 km at 0.05 s, its buffers
    re-established from the live state: c_est carries across the
    segment boundary and the spliced edges read through their own
    latency class."""
    scen = Scenario(events=(LatencyStep(t=0.05, edges=edges_between(
        TOPO, 0, 2), cable_m=1000.0, reestablish=True),))
    fused = _run("fused", _ctrl(1e-7), scen)
    ref = _run("segment-sum", _ctrl(1e-7), scen)
    _same_run(fused, ref)
    assert len(fused.segment_records) == 2
    np.testing.assert_array_equal(fused.lam_eff, ref.lam_eff)


def test_holdover_freezes_c_est():
    """Node 3 in holdover from 0.03 s to 0.07 s: its ν and c_est hold
    (the fused lane's c_est at the end of the holdover is the one it had
    when the loop opened), and the run is segment-sum's."""
    held = Scenario(events=(NodeHoldover(t=0.03, nodes=(3,)),))
    cfg = _cfg(1400)
    fused = _run("auto", _ctrl(1e-7), held, cfg)
    ref = _run("segment-sum", _ctrl(1e-7), held, cfg)
    _same_run(fused, ref)
    before = _run("auto", _ctrl(1e-7), cfg=_cfg(600))
    np.testing.assert_array_equal(fused.c_state["c_est"][:, 3],
                                  before.c_state["c_est"][:, 3])
    rec = fused.freq_ppm[:, 30:, 3]
    np.testing.assert_array_equal(rec, rec[:, :1].repeat(rec.shape[1], 1))
    rejoin = Scenario(events=(NodeHoldover(t=0.03, nodes=(3,)),
                              NodeReset(t=0.05, nodes=(3,))))
    _same_run(_run("auto", _ctrl(1e-7), rejoin, cfg),
              _run("segment-sum", _ctrl(1e-7), rejoin, cfg))


@pytest.mark.parametrize("ctrl,quantize", [
    (ControllerConfig(kind="proportional", kp=2e-8), True),
    (_ctrl(1e-7), False)], ids=["integer-readout", "finc-continuous"])
def test_either_variant_alone_matches_segment_sum(ctrl, quantize):
    """Integer readout under the proportional law, and FINC/FDEC on the
    continuous aggregate: each flag of the period body alone."""
    cfg = SimConfig(dt=5e-5, steps=400, record_every=20,
                    quantize_beta=quantize)
    fused = _run("fused", ctrl, cfg=cfg)
    ref = _run("segment-sum", ctrl, cfg=cfg)
    np.testing.assert_array_equal(fused.freq_ppm, ref.freq_ppm)
    np.testing.assert_array_equal(fused.nu, ref.nu)


def test_auto_dispatches_the_cell_to_fused():
    """The cell's shapes alone pick the fused lane: FC8 at B = 1,024,
    one latency class, β and watermarks, 56 edges read one by one."""
    flags = dict(record_beta=True, record_watermarks=True, edges=128,
                 pulses=True)
    assert select_engine(1024, 128, 1, **flags) == ("fused", 128)
    ppm = np.random.default_rng(1).uniform(-8, 8, (1024, 8)).astype(
        np.float32)
    tr = RunTrace(name="finc")
    res = _run("auto", _ctrl(1e-7), cfg=_cfg(40), ppm=ppm,
               telemetry=Telemetry(beta=True, watermarks=True, trace=tr))
    assert res.engine == "fused" and res.c_state["c_est"].shape == (1024, 8)
    disp, = tr.by_kind("engine_dispatch")
    assert disp.data["controller"] == "discrete"
    assert disp.data["readout"] == "integer"
    assert disp.data["e_pad"] == 128


def test_transfers_count_c_est():
    """c_est starts as zeros made on the device (no upload) and is read
    back once, with ψ and ν, at the end: d2h_bytes counts it."""
    plans._clear_plans()
    tr = RunTrace(name="finc")
    res = _run("auto", _ctrl(1e-7), cfg=_cfg(200), chunk=5,
               telemetry=Telemetry(beta=True, watermarks=True, trace=tr))
    n, r = TOPO.num_nodes, 10
    # Two chunks: the ν and β records and each chunk's four watermarks.
    chunk_reads = (2 * r * B * n + 2 * 4 * B * n) * 4
    assert res.num_launches == 2
    assert tr.counters["d2h_bytes"] == chunk_reads + 3 * B * n * 4
    assert tr.counters["d2h_reads"] == 3
    dense = tr.counters["h2d_bytes"]
    plans._clear_plans()
    tr_p = RunTrace(name="p")
    _run("auto", ControllerConfig(kind="proportional", kp=2e-8),
         cfg=SimConfig(dt=5e-5, steps=200, record_every=20), chunk=5,
         telemetry=Telemetry(beta=True, watermarks=True, trace=tr_p))
    # Integer readout uploads its edge matrices (the one-hot two in
    # bf16, the weighted scatter in f32) and its λeff row.
    e_pad, n_pad = 128, 128
    assert dense - tr_p.counters["h2d_bytes"] == (
        2 * 2 * e_pad * n_pad + 4 * e_pad * n_pad + 4 * e_pad)


@pytest.mark.parametrize("engine", ["tiled", "per-step", "sparse"])
@pytest.mark.parametrize("kind,quantize", [("discrete", True),
                                           ("discrete", False),
                                           ("proportional", True)])
def test_other_lanes_refuse(engine, kind, quantize):
    ctrl = ControllerConfig(kind=kind, kp=2e-8, fs=1e-7)
    cfg = SimConfig(dt=5e-5, steps=40, record_every=20,
                    quantize_beta=quantize)
    with pytest.raises(ValueError, match="fused lane and on the segment-sum"):
        _run(engine, ctrl, cfg=cfg)


def test_refusals_that_stay():
    """PI, FINC/FDEC under the reframing guard, telemetry noise, and a
    fabric whose fused working set does not fit, which dispatch hands to
    a lane without the variant."""
    with pytest.raises(ValueError, match="'pi' runs on the segment-sum"):
        _run("fused", ControllerConfig(kind="pi", kp=2e-8, ki=1e-9))
    with pytest.raises(ValueError, match="FINC/FDEC with a guard"):
        _run("fused", _ctrl(1e-7),
             telemetry=Telemetry(beta=True, guard=True))
    with pytest.raises(ValueError, match="telemetry noise"):
        _run("fused", _ctrl(1e-7), cfg=SimConfig(
            dt=5e-5, steps=40, record_every=20, quantize_beta=True,
            telemetry_noise_ppm=0.01))
    big = ring(300)
    with pytest.raises(ValueError, match="dispatch chose it"):
        run_scenario(big, make_links(big, cable_m=2.0), _ctrl(1e-7),
                     np.zeros((8, 300), np.float32), Scenario(events=()),
                     SimConfig(dt=5e-5, steps=40, record_every=20,
                               quantize_beta=True),
                     options=EngineOptions(engine="auto"), telemetry=TEL)


# SHA-256 of each proportional, continuous-readout kernel's pallas_call
# (its kernel jaxpr, grid mapping and output avals), traced before the
# FINC/FDEC and integer-readout variants came: FC8 with two latency
# classes padded to one (8, 128) tile, 2 records of 4 periods.
PARENT_KERNELS = {
    ("fused", (False, False, False)):
        "70b92779a0f3d84c7d8738d03c1d00138bb2025b6cc3fa2624294e7b5233299e",
    ("fused", (False, False, True)):
        "679cb30836b6e4fb363676967b4be81526faca08e26902fe7072549347488437",
    ("fused", (False, True, False)):
        "1f80e9888c2fff9c3f1b61bfd20a64bbcfe796befb6487c07bf3c06423555dc5",
    ("fused", (False, True, True)):
        "af48970736c9c41b9332fd4760d9d231fd71576e148b3d43dee036b83ad49854",
    ("fused", (True, False, False)):
        "07d6c6259499df35d9523b368b95b384c24a7fd14ff85550a710b76f9cf5fbcd",
    ("fused", (True, False, True)):
        "a464eedc836549d2dd0bb75fc7f901235c565791e8e1f6e47406f41728a79a68",
    ("fused", (True, True, False)):
        "9b4d87b2919fb5b3f870a3cb9ff55b944a65dfaa2aa3344fd63f29ea853cda33",
    ("fused", (True, True, True)):
        "d0876f63cc96f605f6200c3511c7d468b0631619fdb1536ebec4890f028fb276",
    ("tiled", (False, False, False)):
        "e0b8b6da13ed112fd9986f936dc69cfec2e2d254a3839868a2f83745f4fc5662",
    ("tiled", (False, False, True)):
        "9247fa83379afc930bdaf716c98dbd0f906b2d7e3d6852241c1392bcc5a3699e",
    ("tiled", (False, True, False)):
        "9e666cb263ae80d2911096fe8f60c9da03f11281d695b77146a9aacefcb1d374",
    ("tiled", (False, True, True)):
        "c1eed88f93637f579f6f67f57f76deddf20489d7a03948f26eb6198f41e44188",
    ("tiled", (True, False, False)):
        "75ee8154ffe3b67fe6d2e1e1412a257844644bde1b7160c68dafe6c4bcefb1a6",
    ("tiled", (True, False, True)):
        "1a6504386444144df9f309aaf1b0965643d8446f7354e27c08c2cd75d615cd2f",
    ("tiled", (True, True, False)):
        "e3253638792ac55a2ab43939448f45e9f101fac206f723bb206ab16cbed84c9a",
    ("tiled", (True, True, True)):
        "45bab02d2e3ddba477cc4ec0ba37266206d54f6de7daaf6e1839f2855a06e8ae",
}


@functools.lru_cache(maxsize=None)
def _two_class_fc8():
    e = TOPO.num_edges
    links = make_links(TOPO, cable_m=np.where(np.arange(e) % 3, 2.0, 10.0),
                       beta0=(np.arange(e) % 5) - 2.0)
    return densify(TOPO, links, omega_nom=125e6)


@pytest.mark.parametrize(
    "lane,flags", sorted(PARENT_KERNELS),
    ids=[f"{lane}-b{f[0]:d}w{f[1]:d}g{f[2]:d}"
         for lane, f in sorted(PARENT_KERNELS)])
def test_proportional_kernels_unchanged(lane, flags):
    a, lam, classes, n_pad = _two_class_fc8()
    rb, rw, rg = flags
    kw = dict(num_records=2, record_every=4, record_beta=rb,
              record_watermarks=rw, record_guard=rg, interpret=False)
    if rg:
        kw.update(guard_lo=-1e9, guard_hi=1e9, guard_stop=2)
    if lane == "tiled":
        fn = functools.partial(bittide_tiled_fused_pallas, tile_j=128, **kw)
    else:
        fn = functools.partial(bittide_fused_pallas, **kw)
    z = jnp.zeros((8, n_pad), jnp.float32)
    args = (z, z, z, a, a.sum(axis=(0, 2)).reshape(1, -1),
            lam.sum(axis=(0, 2)), classes, jnp.full((8,), 2e-8), 0.0)
    jx = jax.make_jaxpr(lambda *x: fn(*x, 125e3))(*args)
    eqn, = [q for q in jx.jaxpr.eqns if q.primitive.name == "pallas_call"]
    text = "\n".join(str(eqn.params[k])
                     for k in ("jaxpr", "grid_mapping", "out_avals"))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_KERNELS[(lane, flags)]

