"""The cell ``testbed.finc_mc`` on the program's own lane: FINC/FDEC with
integer readout, which dispatch (``engine="auto"``) puts on the fused
Pallas lane, run here in the Pallas interpreter.

The harness's FINC/FDEC comparison (``compare.PulseReplay``) holds the
fused lane as ``test_chipbench_discrete.py`` holds segment-sum: a sound
run passes, and each fault the testbed under FINC/FDEC can have fails.
The cell is read from its own files (``configs/testbed_fc8_finc.json``,
``traffic/finc_mc.json``) in a temporary copy of the benchmark, cut to
B = 64 draws and 2,000 periods with the limits of
``finc_limits_2000.json``; at the mix's own 10,000 periods the cell's
own limits (``limits/testbed.finc_mc.json``).  Besides: the cell's
shape, the ``fused_pulse`` count and the ``pulse_roofline`` reader.
"""
import dataclasses
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

import _chipbench_path  # noqa: F401
import test_chipbench_discrete as discrete
from chipbench import generator, harness, program
from chipbench.harness import Readings
from chipbench.spec import Spec
from test_chipbench_discrete import (CELL, DATA, DRAWS, PERIODS, SEED,
                                     STEPS, run)
from test_chipbench_discrete import clear_runs  # noqa: F401  (fixture)

ROOT = _chipbench_path.ROOT
BENCH = ROOT / "benchmarks/chip"
CONFIG = BENCH / "configs/testbed_fc8_finc.json"
MIX = BENCH / "traffic/finc_mc.json"
SHAPE = {"draws": 1024, "nodes": 8, "edges": 56, "classes": 1,
         "periods": 10000, "records": 500, "measure": True}


def make_spec(tmp: Path, fs: float, periods: int = PERIODS,
              limits: str | None = "finc_limits_2000.json") -> Spec:
    """A copy of the benchmark with the cell at step ``fs`` and B = 64,
    and the limits of ``tests/data/<limits>`` (None: the cell's own)."""
    bench = tmp / "benchmarks/chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads(CONFIG.read_text())
    cfg["controller"]["fs"] = fs
    cfg["duration_s"] = periods * cfg["dt_s"]
    (bench / "configs/testbed_fc8_finc.json").write_text(json.dumps(cfg))
    mix = json.loads(MIX.read_text())
    mix["draws"] = DRAWS
    (bench / "traffic/finc_mc.json").write_text(json.dumps(mix))
    if limits is not None:
        shutil.copy(DATA / limits, bench / f"limits/{CELL}.json")
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return Spec(tmp)


@pytest.fixture(scope="module", params=STEPS, ids=["fs1e-8", "fs1e-7"])
def spec(request, tmp_path_factory):
    return make_spec(tmp_path_factory.mktemp("bench"), request.param)


def on_fused(monkeypatch) -> None:
    """Run the harness's program as it stands (``engine="auto"``) in two
    launches a call, and check that dispatch chose the fused lane."""
    from repro.kernels import EngineOptions

    real_init, real_call = program.Program.__init__, program.Program.call

    def init(self, config, traffic, kinds):
        real_init(self, config, traffic, kinds)
        records = self.cfg.steps // self.cfg.record_every
        assert self.options.engine == "auto"
        self.options = EngineOptions(engine="auto",
                                     chunk_records=records // 2)

    def call(self, ppm, trace=None):
        res = real_call(self, ppm, trace=trace)
        assert res.engine == "fused"
        return res

    monkeypatch.setattr(program.Program, "__init__", init)
    monkeypatch.setattr(program.Program, "call", call)


@pytest.fixture(autouse=True)
def fused(monkeypatch):
    on_fused(monkeypatch)


def _floor_pulses(c_rel, c_est, pulses):
    """``period.finc_fdec`` with ``floor`` where the rule rounds."""
    import jax.numpy as jnp
    n = jnp.clip(jnp.floor((c_rel - c_est) / pulses.fs), -pulses.budget,
                 pulses.budget)
    return c_est + n * pulses.fs


def plant(monkeypatch, fault: str) -> None:
    """``test_chipbench_discrete.plant`` on the fused lane: the floor goes
    into the kernel's pulse rule, and the reset restarts ``c_est`` at 0 in
    every launch after the first (the second starts from the first's ψ
    and ν alone); ``continuous`` and ``step`` change the program's
    configuration, whatever its lane."""
    import jax
    from repro.kernels import period
    from repro.scenarios import runner
    if fault == "floor":
        monkeypatch.setattr(period, "finc_fdec", _floor_pulses)
        jax.clear_caches()
    elif fault == "reset":
        real_engine = runner._fused_engine

        def restart_engine(psi, *args, c_est=None, **kw):
            if c_est is not None and np.asarray(psi).any():
                c_est = np.zeros_like(c_est)
            return real_engine(psi, *args, c_est=c_est, **kw)

        monkeypatch.setattr(runner, "_fused_engine", restart_engine)
    else:
        discrete.plant(monkeypatch, fault)


def test_sound_run_is_correct(spec):
    discrete.test_sound_run_is_correct(spec)


@pytest.mark.parametrize("fault,number", [
    ("floor", "pulse_records"), ("continuous", "pulse_records"),
    # The count recovers within two periods of a reset; ψ keeps the slip.
    ("reset", "beta_frames"), ("step", "pulse_records")])
def test_fault_is_not_correct(spec, monkeypatch, clear_runs, fault, number):
    plant(monkeypatch, fault)
    line = run(spec)
    assert line["correct"] is False, line["checks"]
    assert line["failed"] >= 1
    check = line["checks"][number]
    assert check["value"] > check["limit"], line["checks"]


def test_budget_never_binds(spec):
    """The pulse budget left unclipped gives the same answer to the bit on
    the fused lane too."""
    c = spec.cell(CELL)
    prog = program.Program(c.config, c.traffic, c.kinds)
    ppm = generator.draws(c.config, c.traffic, c.kinds, SEED, 0)
    sound = prog.answer(prog.call(ppm))
    prog.ctrl = dataclasses.replace(prog.ctrl, pulses_per_update=10**9)
    unclipped = prog.answer(prog.call(ppm))
    for k in ("freq_ppm", "beta", "beta_abs_max", "nu_min_ppm",
              "nu_max_ppm"):
        assert np.array_equal(sound[k], unclipped[k]), k


@pytest.mark.parametrize("fs", STEPS, ids=["fs1e-8", "fs1e-7"])
def test_reset_fails_at_the_mix_length(tmp_path, monkeypatch, fs):
    """At the mix's own 10,000 periods a sound fused run passes the cell's
    own limits, and a c_est reset where the run is split fails
    ``beta_frames``."""
    spec = make_spec(tmp_path, fs, periods=10_000, limits=None)
    line = run(spec)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["pulse_records"]["value"] == 0
    plant(monkeypatch, "reset")
    line = run(spec)
    assert line["correct"] is False, line["checks"]
    check = line["checks"]["beta_frames"]
    assert check["value"] > check["limit"], line["checks"]


def test_controller_kind_as_files_on_the_fused_lane(tmp_path, monkeypatch):
    """``test_controller_kind_as_files`` on the program's own lane: the
    FINC/FDEC kind in the boards' units, from a path of its own, runs on
    the fused lane and its pulses hold through the replay."""
    import test_chipbench_kinds
    monkeypatch.setattr(discrete, "on_segment_sum", lambda mp: None)
    test_chipbench_kinds.test_controller_kind_as_files(tmp_path, monkeypatch)


def test_shape_of_the_cell():
    c = Spec(ROOT).cell(CELL)
    assert harness._shape(c.config, c.traffic, c.kinds) == SHAPE


def test_fused_pulse_count():
    # Every period a source and a destination gather of the 56 edges
    # from 8 nodes and their scatter back; a measure sweep of the 8x8
    # class per record; the adjacency, three edge matrices and λeff once,
    # the ν and β records once.
    lane = Spec(ROOT).lanes()["fused_pulse"]
    assert lane.count(SHAPE) == {
        "flops": 2 * 1024 * 8 * 56 * 3 * 10000 + 2 * 1024 * 64 * 500,
        "bytes": 4 * (64 + 8 * 56 * 3 + 56) + 4 * 1024 * 8 * 500 * 2}
    assert lane.TRACE_NAMES


def test_pulse_roofline_reads_the_fused_pulse_count():
    """``pulse_roofline``: the fused kernel's trace time against the
    ``fused_pulse`` lane's count, and None off the fused lane or where no
    profile was taken."""
    from chipbench.trace import Summary
    spec = Spec(ROOT)
    lanes = spec.lanes()
    peaks = spec.load_json("", "peaks.json")["TPU v5 lite"]
    kernel_s = {lane: 0.2 for lane in lanes}
    prof = Summary(window_s=3.0, busy_s=2.9, calls=2, kernel_s=kernel_s,
                   device_ops=[], idle_gaps=[])
    r = Readings(calls=[], lane="fused", shape=SHAPE, peaks=peaks,
                 count=lambda lane: lanes[lane].count(SHAPE), profile=prof)
    c = lanes["fused_pulse"].count(SHAPE)
    least = max(c["flops"] / peaks["bf16_flops_per_s"],
                c["bytes"] / peaks["hbm_bytes_per_s"])
    reader = spec.reader("pulse_roofline")
    assert reader.read(r) == pytest.approx(100 * least * 2 / 0.2)
    assert reader.read(dataclasses.replace(r, lane="tiled")) is None
    assert reader.read(dataclasses.replace(r, profile=None)) is None
