"""The comparison that decides ``correct``, run through the harness on the
CPU at a test size: a sound run passes the cell's own limits, and the
control and each fault the cells can have fail them.

The harness's look for a chip is skipped (``run_cell`` is handed a
device); everything else of a run happens: set-up, the window, the
sampled answers, the reference and the comparison.  The cell is
``testbed.splice_mc`` with its mix cut to 8 draws in a temporary copy of
the benchmark; the configuration, events and limits are the cell's own.
"""
import dataclasses
import json
import shutil
import time

import numpy as np
import pytest

import _chipbench_path  # noqa: F401
from chipbench import compare, harness, program
from chipbench.spec import Spec

ROOT = _chipbench_path.ROOT
CELL = "testbed.splice_mc"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def small_spec(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "benchmarks/chip", tmp / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp)
    mix = json.loads((ROOT / "benchmarks/chip/traffic/splice_mc.json")
                     .read_text())
    mix["draws"] = 8
    (tmp / "benchmarks/chip/traffic/splice_mc.json").write_text(
        json.dumps(mix))
    return Spec(tmp)


def _run(spec, seed=2**31 + 17):
    return harness.run_cell(spec, CELL, seed, 0.5, False,
                            time.perf_counter(), CPU)


def test_sound_run_is_correct(small_spec):
    line = _run(small_spec)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"freq_ppm", "beta_frames",
                                   "beta_peak_frames", "nu_extremes_ppm"}


def _unchanged(res, ppm):
    """The step returns its state unchanged: ν stays ν_u, ψ stays 0."""
    from repro.telemetry import Watermarks
    freq = np.broadcast_to(ppm[:, None, :], res.freq_ppm.shape).copy()
    beta = np.zeros_like(res.beta)
    return dataclasses.replace(
        res, freq_ppm=freq, beta=beta,
        watermarks=Watermarks.from_record(beta, freq))


def _half_batch(res, ppm, call):
    """Half of the draws left out; their rows take the mean of the rest."""
    half = call(ppm[: len(ppm) // 2])
    from repro.telemetry import Watermarks

    def fill(x):
        x = np.asarray(x, np.float64)
        mean = x.mean(axis=0, keepdims=True)
        return np.concatenate([x, np.repeat(mean, len(ppm) - len(x), 0)])

    freq, beta = fill(half.freq_ppm), fill(half.beta)
    return dataclasses.replace(
        res, freq_ppm=freq, beta=beta,
        watermarks=Watermarks.from_record(beta, freq))


def _altered(res, ppm):
    """One answer altered where it is produced: one ν record of one node
    moved by 1e-4 ppm."""
    freq = np.array(res.freq_ppm)
    freq[0, -1, 3] += 1e-4
    return dataclasses.replace(res, freq_ppm=freq)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_not_correct(small_spec, monkeypatch, fault):
    real = program.Program.call

    def broken(self, ppm, trace=None):
        res = real(self, ppm, trace=trace)
        if fault == "unchanged":
            return _unchanged(res, ppm)
        if fault == "half_batch":
            return _half_batch(res, ppm, lambda p: real(self, p))
        return _altered(res, ppm)

    monkeypatch.setattr(program.Program, "call", broken)
    line = _run(small_spec)
    assert line["correct"] is False, line["checks"]
    assert line["failed"] >= 1


def test_splice_not_reestablished_is_not_correct(small_spec, monkeypatch):
    """The splice layer at fault: the §5.6 splice keeps the old λeff
    instead of re-establishing the buffers; β after the splice shows it."""
    real = program.Program.__init__

    def keep_lam(self, config, traffic):
        real(self, config, traffic)
        self.scenario = dataclasses.replace(self.scenario, events=tuple(
            dataclasses.replace(ev, reestablish=False)
            for ev in self.scenario.events))

    monkeypatch.setattr(program.Program, "__init__", keep_lam)
    line = _run(small_spec)
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["beta_frames"]["value"] > 1.0


@pytest.mark.parametrize("cell", ["testbed.splice_mc", "torus22.free"])
@pytest.mark.parametrize("seed", [5, 6, 2**31 + 3])
def test_control_is_not_correct(cell, seed):
    """The reference at Precision.HIGH, in the program's place, fails the
    cell's own limits at the cell's own size."""
    import control
    c = Spec(ROOT).cell(cell)
    correct, failed, checks = compare.judge(control.control_gaps(c, seed),
                                            c.limits)
    assert not correct, checks
    assert failed == int(c.traffic["check_calls"])
