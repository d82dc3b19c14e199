"""The guard's comparison, run through the harness on the CPU at a test
size: a sound guarded run passes the cell's limits, and each fault a
guard can have fails them; a decision the reference cannot tell is
replayed and counted, not failed.

The cell is a guarded torus3d(4) defined in a temporary copy of the
benchmark: the Fig-18 torus configuration with its torus cut to k = 4,
and the free mix recorded every 4 periods with a 7-frame margin, which
covers one record's occupancy slew at +-8 ppm (under 7 frames per link
of a node).  Its net occupancy stays inside +-14 frames per link, and
about half of the guard's decisions trip: the band is tested from both
sides.  The limits are ``torus22.free``'s with the guard's two exact
numbers.
"""
import dataclasses
import json
import shutil
import time

import numpy as np
import pytest

import _chipbench_path  # noqa: F401
from chipbench import compare, harness, program, reference
from chipbench.spec import Spec

ROOT = _chipbench_path.ROOT
CELL = "torus4.guard"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
GUARD_NUMBERS = {"trip_records", "shift_frames"}
RECORD_EVERY = 4
MARGIN = 7.0


@pytest.fixture(scope="module")
def small_spec(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    bench = tmp / "benchmarks/chip"
    shutil.copytree(ROOT / "benchmarks/chip", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((bench / "configs/torus3d_22.json").read_text())
    cfg.update(name="torus3d_4", topology={"kind": "torus3d", "k": 4})
    (bench / "configs/torus3d_4.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic/free.json").read_text())
    mix.update(record_every=RECORD_EVERY, guard={"margin_frames": MARGIN})
    (bench / "traffic/guard_test.json").write_text(json.dumps(mix))
    lim = json.loads((bench / "limits/torus22.free.json").read_text())
    lim.update(trip_records=0, shift_frames=0)
    (bench / f"limits/{CELL}.json").write_text(json.dumps(lim))
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"].append({"name": "torus3d_4", "source": "test",
                            "file": "benchmarks/chip/configs/torus3d_4.json",
                            "reduced": ["topology", "oscillator_ppm"],
                            "why": "test"})
    data["workloads"].append({"name": CELL, "config": "torus3d_4",
                              "traffic": "guard_test", "chips": 1,
                              "why": "test"})
    for m in data["end_to_end"]:
        if m["name"] == "node_periods_per_s":
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(data))
    return Spec(tmp)


def _run(spec, seed=2**31 + 29):
    return harness.run_cell(spec, CELL, seed, 0.3, False,
                            time.perf_counter(), CPU)


def test_sound_guarded_run_is_correct(small_spec):
    line = _run(small_spec)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert GUARD_NUMBERS <= set(line["checks"])
    assert line["checks"]["trip_records"]["value"] == 0
    assert line["checks"]["shift_frames"]["value"] == 0
    for counts in line["decisions"].values():
        # Held trips, and held decisions not to trip, in every call.
        assert counts["trips_held"] > counts["no_trips_held"] >= 1
        assert counts["potentials_held"] > counts["potentials_replayed"]


def test_unguarded_cells_compare_four_numbers(small_spec):
    c = small_spec.cell("torus22.free")
    assert not c.traffic.get("guard")
    assert set(c.limits) == {"freq_ppm", "beta_frames", "beta_peak_frames",
                             "nu_extremes_ppm"}


def _one_edge_off(real):
    """Every rotation moves edge 0 of each rotated draw one frame more
    than the rule gives: in its λeff and in the reported shift."""
    def broken(*args, **kw):
        lam, shift = real(*args, **kw)
        lam, shift = np.array(lam), np.array(shift)
        rows = np.abs(shift).sum(axis=-1) > 0
        shift[rows, 0] += 1
        lam[rows, 0] += 1
        return lam, shift
    return broken


def _left_out(real):
    """The rotation is computed and reported, but λeff keeps its value."""
    def broken(topo, lam_eff, *args, **kw):
        _, shift = real(topo, lam_eff, *args, **kw)
        rows = np.asarray(shift).shape[0]
        lam = np.asarray(lam_eff, np.float64)
        return (np.tile(lam, (rows, 1)) if lam.ndim == 1 else lam), shift
    return broken


@pytest.mark.parametrize("fault", ["shift_off_by_one", "rotation_left_out"])
def test_rotation_fault_is_not_correct(small_spec, monkeypatch, fault):
    from repro.scenarios import runner
    real = runner._rotation_shifts
    wrap = _one_edge_off if fault == "shift_off_by_one" else _left_out
    monkeypatch.setattr(runner, "_rotation_shifts", wrap(real))
    line = _run(small_spec)
    assert line["correct"] is False, line["checks"]
    if fault == "shift_off_by_one":
        assert line["checks"]["shift_frames"]["value"] >= 1
    else:
        assert line["checks"]["beta_frames"]["value"] > 1.0


@pytest.mark.parametrize("fault", ["margin_ignored",
                                   "band_not_scaled_by_degree"])
def test_band_fault_is_not_correct(small_spec, monkeypatch, fault):
    """The in-kernel guard band planted wrong: at depth/2 where the
    margin should take it to depth/2 - margin, or g in place of g.deg
    (the torus's degree is 6)."""
    from repro.scenarios import runner
    real = runner._guard_band_cols

    def broken(b_pad, b, target, guard_rows, *args, **kw):
        rows = np.asarray(guard_rows, np.float64)
        rows = rows + MARGIN if fault == "margin_ignored" else rows / 6.0
        return real(b_pad, b, target, rows, *args, **kw)

    monkeypatch.setattr(runner, "_guard_band_cols", broken)
    line = _run(small_spec)
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["trip_records"]["value"] >= 1


def test_trip_one_record_late_is_not_correct(small_spec, monkeypatch):
    """Every rotation reported one record after the record that
    tripped."""
    real = program.Program.call

    def late(self, ppm, trace=None):
        res = real(self, ppm, trace=trace)
        return dataclasses.replace(res, reframes=[
            dataclasses.replace(r, record=r.record + 1)
            for r in res.reframes])

    monkeypatch.setattr(program.Program, "call", late)
    line = _run(small_spec)
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["trip_records"]["value"] >= 1


@pytest.mark.parametrize("seed", [5, 2**31 + 3])
def test_control_is_not_correct_on_the_small_torus(small_spec, seed):
    """The reference at Precision.HIGH, with its own trips and rotations,
    in the program's place."""
    import control
    c = small_spec.cell(CELL)
    correct, failed, checks = compare.judge(control.control_gaps(c, seed),
                                            c.limits)
    assert not correct, checks
    assert failed >= 1


def _ring_fabric(n=8):
    pairs = [(i, (i + 1) % n) for i in range(n)]
    pairs = [p for a, b in pairs for p in ((a, b), (b, a))]
    src, dst = np.array(pairs).T
    return reference.Fabric(nodes=n, src=src, dst=dst,
                            lat_frames=np.zeros(len(src)), transitive=True)


def test_replay_counts_decisions_inside_the_bound():
    """A trip and a potential on which the reference cannot tell are
    adopted from the program and counted; held ones are compared."""
    fab = _ring_fabric()
    limit = 1e-3
    # The program rounded node 3's potential 0.5 + 1e-4 down to 0 and
    # the rest as the reference does; it rotated draw 1 at record 4.
    x = np.array([2.2, -1.3, 0.1, 0.5001, 0.0, -0.7, -1.9, 1.0999])
    prog_pot = np.rint(x)
    prog_pot[3] = np.floor(x[3])
    shift = np.zeros((2, len(fab.src)), np.int64)
    shift[1] = prog_pot[fab.src] - prog_pot[fab.dst]
    got = {"reframes": [(5, shift)], "edges": np.stack([fab.src, fab.dst], 1)}
    replay = compare.Replay(got, {"beta_frames": limit})
    replay.bind(fab, pinv_norm=1.0)       # rounding bound 2e-3 frames
    # Draw 0: the reference says "trip" by 1e-4 frames, inside the bound:
    # replayed as the program's "no trip".  Draw 1: held, and agrees.
    applied = replay.trips(4, np.array([True, True]),
                           np.array([1e-4, 5.0]))
    assert applied.tolist() == [False, True]
    pot = replay.potentials(4, 1, x)
    assert np.array_equal(pot, prog_pot)
    assert replay.counts == {"trips_held": 1, "no_trips_held": 0,
                             "trips_replayed": 1,
                             "potentials_held": 7,
                             "potentials_replayed": 1}
    assert replay.numbers() == {"trip_records": 0, "shift_frames": 0.0}


def test_replay_holds_decisions_outside_the_bound():
    """The same program decisions where the reference can tell them:
    both fail."""
    fab = _ring_fabric()
    x = np.array([2.2, -1.3, 0.1, 0.56, 0.0, -0.7, -1.9, 1.04])
    prog_pot = np.rint(x)
    prog_pot[3] -= 1
    shift = np.zeros((2, len(fab.src)), np.int64)
    shift[1] = prog_pot[fab.src] - prog_pot[fab.dst]
    got = {"reframes": [(5, shift)], "edges": np.stack([fab.src, fab.dst], 1)}
    replay = compare.Replay(got, {"beta_frames": 1e-3})
    replay.bind(fab, pinv_norm=1.0)
    replay.trips(4, np.array([True, True]), np.array([0.5, 5.0]))
    replay.potentials(4, 1, x)
    assert replay.numbers() == {"trip_records": 1, "shift_frames": 1.0}
