"""The FINC/FDEC comparison (``compare.PulseReplay``), run through the
harness on the CPU: a sound run of the repo's segment-sum engine passes
and holds at least the floor, and the control and each fault the
testbed under FINC/FDEC can have fail.

The cell is the configuration and mix of ``tests/data``
(``testbed_fc8_finc.json``, ``finc_mc.json``) in a temporary copy of the
benchmark: FC8, 2 m, ±8 ppm, kp = 2e-8, 50 pulses per 50 µs period,
integer readout, records every 20 periods, cut to B = 64 draws and
2,000 periods, with the step fs at 0.01 and 0.1 ppm, and the limits of
``finc_limits_2000.json``, set from readings at that size; at the mix's
own 10,000 periods those of ``finc_limits_10000.json``.  The program is
``run_scenario`` on the segment-sum engine, the one that runs FINC/FDEC
today, in two launches a call; its per-edge β record is folded to the
per-node net the kernel lanes record.
"""
import dataclasses
import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import _chipbench_path  # noqa: F401
from chipbench import compare, generator, harness, program, reference
from chipbench.spec import Spec

ROOT = _chipbench_path.ROOT
DATA = Path(__file__).resolve().parent / "data"
CELL = "testbed.finc_mc"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
DRAWS = 64
PERIODS = 2000
SEED = 2**31 + 41
STEPS = [1e-8, 1e-7]


def make_spec(tmp: Path, fs: float, periods: int = PERIODS,
              limits: str = "finc_limits_2000.json") -> Spec:
    """A copy of the benchmark with the FINC/FDEC cell at step ``fs``."""
    bench = tmp / "benchmarks/chip"
    shutil.copytree(ROOT / "benchmarks/chip", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((DATA / "testbed_fc8_finc.json").read_text())
    cfg["controller"]["fs"] = fs
    cfg["duration_s"] = periods * cfg["dt_s"]
    (bench / "configs/testbed_fc8_finc.json").write_text(json.dumps(cfg))
    mix = json.loads((DATA / "finc_mc.json").read_text())
    mix["draws"] = DRAWS
    (bench / "traffic/finc_mc.json").write_text(json.dumps(mix))
    shutil.copy(DATA / limits, bench / f"limits/{CELL}.json")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"].append({
        "name": "testbed_fc8_finc", "source": "test",
        "file": "benchmarks/chip/configs/testbed_fc8_finc.json",
        "reduced": ["duration_s"], "why": "test"})
    data["workloads"].append({"name": CELL, "config": "testbed_fc8_finc",
                              "traffic": "finc_mc", "chips": 1,
                              "why": "test"})
    for m in data["end_to_end"]:
        if m["name"] in ("draws_per_s", "scenario_p95_ms"):
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(data))
    return Spec(tmp)


@pytest.fixture(scope="module", params=STEPS, ids=["fs1e-8", "fs1e-7"])
def spec(request, tmp_path_factory):
    return make_spec(tmp_path_factory.mktemp("bench"), request.param)


def on_segment_sum(monkeypatch) -> None:
    """Run the harness's program on the segment-sum engine, two launches
    a call, its β record folded by destination."""
    from repro.core.reframing import node_net_occupancy
    from repro.kernels import EngineOptions

    real_init, real_call = program.Program.__init__, program.Program.call

    def init(self, config, traffic, kinds):
        real_init(self, config, traffic, kinds)
        records = self.cfg.steps // self.cfg.record_every
        self.options = EngineOptions(engine="segment-sum",
                                     chunk_records=records // 2)

    def call(self, ppm, trace=None):
        res = real_call(self, ppm, trace=trace)
        return dataclasses.replace(
            res, beta=node_net_occupancy(res.topo, res.beta))

    monkeypatch.setattr(program.Program, "__init__", init)
    monkeypatch.setattr(program.Program, "call", call)


@pytest.fixture(autouse=True)
def segment_sum(monkeypatch):
    on_segment_sum(monkeypatch)


def run(spec, seed=SEED):
    return harness.run_cell(spec, CELL, seed, 0.3, False,
                            time.perf_counter(), CPU)


def test_sound_run_is_correct(spec):
    line = run(spec)
    checks = line["checks"]
    assert line["correct"] is True, checks
    assert checks["pulse_records"]["value"] == 0
    assert checks["replayed_share"]["limit"] == 1 - compare.HELD_FLOOR
    assert checks["replayed_share"]["value"] <= 1 - compare.HELD_FLOOR
    for counts in line["decisions"].values():
        assert counts["records_held"] >= counts["records_replayed"]


def _floor_step(cfg, state, agg_err, kp=None):
    """``controller_step`` with ``floor`` where the rule rounds."""
    import jax.numpy as jnp
    c_est = state["c_est"]
    want = jnp.floor((kp * agg_err - c_est) / cfg.fs)
    pulses = jnp.clip(want, -cfg.pulses_per_update, cfg.pulses_per_update)
    c_est = c_est + pulses * cfg.fs
    return {**state, "c_est": c_est}, c_est


def plant(monkeypatch, fault: str) -> None:
    """Break the program underneath the harness.

    ``floor``       the pulse count rounded down, not to the nearest
    ``continuous``  the occupancy read continuous where the configuration
                    reads whole frames
    ``reset``       c_est back at 0 where a call's run is split (the
                    second launch starts from the first's ψ and ν alone)
    ``step``        fs 10 % larger than the configuration's
    """
    from repro.core import frame_model
    from repro.scenarios import runner
    if fault == "floor":
        monkeypatch.setattr(frame_model, "controller_step", _floor_step)
        # JAX traces the step once per controller: drop what it traced
        # (and ``clear_runs`` again once the fault is undone).
        jax.clear_caches()
        return
    if fault == "reset":
        real = runner.simulate_ensemble

        def restart(*args, init=None, **kw):
            if init is not None:
                init = (init.psi, init.nu, {k: np.zeros_like(v) for k, v
                                            in init.c_state.items()})
            return real(*args, init=init, **kw)

        monkeypatch.setattr(runner, "simulate_ensemble", restart)
        return
    real_init = program.Program.__init__

    def init(self, config, traffic, kinds):
        real_init(self, config, traffic, kinds)
        if fault == "continuous":
            self.cfg = dataclasses.replace(self.cfg, quantize_beta=False)
        elif fault == "step":
            self.ctrl = dataclasses.replace(self.ctrl, fs=self.ctrl.fs * 1.1)
        else:
            raise ValueError(fault)

    monkeypatch.setattr(program.Program, "__init__", init)


@pytest.fixture
def clear_runs():
    """What JAX traced under a planted fault goes with the test."""
    yield
    jax.clear_caches()


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
    """The pulse budget left unclipped gives the same answer to the bit:
    at 50 pulses a period this traffic never asks for more, so no
    comparison can see that fault here."""
    c = spec.cell(CELL)
    prog = program.Program(c.config, c.traffic, c.kinds)
    ppm = generator.draws(c.config, c.traffic, c.kinds, SEED, 0)
    sound = prog.answer(prog.call(ppm))
    prog.ctrl = dataclasses.replace(prog.ctrl, pulses_per_update=10**9)
    unclipped = prog.answer(prog.call(ppm))
    for k in ("freq_ppm", "beta", "beta_abs_max", "nu_min_ppm",
              "nu_max_ppm"):
        assert np.array_equal(sound[k], unclipped[k]), k


@pytest.mark.parametrize("seed", [5, 2**31 + 3, 2**31 + 77])
@pytest.mark.parametrize("fs", STEPS, ids=["fs1e-8", "fs1e-7"])
def test_control_is_not_correct(tmp_path, fs, seed):
    """The reference one precision down (Precision.HIGH, each edge read
    from the bf16_3x neighbour term) in the program's place, at the mix's
    10,000 periods: once the loop dithers, its edges sit on the rounding
    boundaries at the records, and the control's readouts there differ
    from the reference's by more than the bound."""
    import control
    c = make_spec(tmp_path, fs, periods=10_000,
                  limits="finc_limits_10000.json").cell(CELL)
    correct, failed, checks = compare.judge(control.control_gaps(c, seed),
                                            c.limits)
    assert not correct, checks
    assert checks["pulse_records"]["value"] >= 100


def test_replay_that_holds_too_little_is_not_correct(tmp_path):
    """A comparison that replayed most node-records checked too little:
    it reads as not correct, whatever a limits file says."""
    c = make_spec(tmp_path, STEPS[1]).cell(CELL)
    fab = reference.build_fabric(c.config, c.kinds)
    nsum = reference._NodeSum(fab.dst, fab.nodes)
    replay = compare.PulseReplay({"freq_ppm": np.zeros((1, 1, 8)),
                                  "beta": np.zeros((1, 1, 8))})
    replay.bind(fab, reference.Laplacian(fab, nsum), nsum, (1, 1, 8),
                reference.controller(c.config, c.kinds, fab, np.float64))
    own = got = np.zeros((1, 8))
    replay.decide(0, own, got, np.arange(8)[None] < 2, own)
    gaps = {"freq_ppm": 0.0, **replay.numbers()}
    assert gaps == {"freq_ppm": 0.0, "pulse_records": 0,
                    "replayed_share": 0.75}
    for limits in ({"freq_ppm": 1.0, "pulse_records": 0},
                   {"freq_ppm": 1.0, "pulse_records": 0,
                    "replayed_share": 1.0}):
        correct, failed, checks = compare.judge([gaps], limits)
        assert not correct and failed == 1
        assert checks["replayed_share"]["limit"] == 1 - compare.HELD_FLOOR


def test_pulses_need_integer_readout(tmp_path):
    """The replay's bound is written for pulses read from whole frames:
    the comparison refuses FINC/FDEC with continuous readout."""
    c = make_spec(tmp_path, STEPS[1]).cell(CELL)
    cfg = {**c.config, "controller": {**c.config["controller"],
                                      "readout": "continuous"}}
    ppm = generator.draws(cfg, c.traffic, c.kinds, SEED, 0)
    got = reference.simulate(cfg, c.traffic, c.kinds, ppm)
    with pytest.raises(ValueError, match="integer readout"):
        compare.check_call(cfg, c.traffic, c.kinds, ppm, got, c.limits)


@pytest.mark.parametrize("fs", STEPS, ids=["fs1e-8", "fs1e-7"])
def test_reset_fails_at_the_mix_length(tmp_path, monkeypatch, fs):
    """At the mix's own 10,000 periods, where the loop dithers, a sound run
    passes ``finc_limits_10000.json`` and a c_est reset where the run is
    split fails ``beta_frames``: with a record every 20 periods the
    reference runs too briefly alone to drift from a dithering program by
    the slip a reset leaves in ψ."""
    spec = make_spec(tmp_path, fs, periods=10_000,
                     limits="finc_limits_10000.json")
    line = run(spec)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["pulse_records"]["value"] == 0
    plant(monkeypatch, "reset")
    line = run(spec)
    assert line["correct"] is False, line["checks"]
    check = line["checks"]["beta_frames"]
    assert check["value"] > check["limit"], line["checks"]
