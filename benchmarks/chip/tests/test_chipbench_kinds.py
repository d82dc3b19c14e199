"""Fabric, controller and event kinds are files: a new kind needs files
and ``BENCHMARK.json`` entries only, and the kinds in use give the
reference the outputs it gave before they moved into files (the
FINC/FDEC kind's from when it was added)."""
import hashlib
import json
import shutil
import time

import numpy as np
import pytest

import _chipbench_path  # noqa: F401
from chipbench import generator, harness, program, reference
from chipbench.spec import Spec

ROOT = _chipbench_path.ROOT
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

RING = '''"""Ring of n nodes, each linked to the next (``{"kind": "ring",
"n": n}``)."""
import numpy as np

TRANSITIVE = True


def nodes(t):
    return int(t["n"])


def pairs(t):
    n = nodes(t)
    return np.asarray([p for i in range(n)
                       for p in ((i, (i + 1) % n), ((i + 1) % n, i))])


def program(t):
    from repro.core.topology import ring
    return ring(nodes(t))
'''


def test_topology_kind_as_files(tmp_path):
    """A ring, which no harness file knows: a kind file, a configuration,
    a guarded mix and limits in a path of their own.  The harness builds
    the program's fabric from it, runs the reference and compares."""
    shutil.copytree(ROOT / "benchmarks/chip", tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    extra = tmp_path / "extra"
    for kind in ("kinds/topology", "configs", "traffic", "limits"):
        (extra / kind).mkdir(parents=True)
    (extra / "kinds/topology/ring.py").write_text(RING)
    cfg = json.loads((ROOT / "benchmarks/chip/configs/testbed_fc8.json")
                     .read_text())
    cfg.update(name="ring8", topology={"kind": "ring", "n": 8},
               duration_s=0.2)
    (extra / "configs/ring8.json").write_text(json.dumps(cfg))
    (extra / "traffic/ring_guard.json").write_text(json.dumps(
        {"draws": 4, "record_every": 20, "events": [], "check_calls": 1,
         "telemetry": {"beta": True, "watermarks": True},
         "guard": {"margin_frames": 1.0}}))
    (extra / "limits/ring8.guard.json").write_text(json.dumps(
        {"freq_ppm": 1e-5, "beta_frames": 1e-2, "beta_peak_frames": 1e-2,
         "nu_extremes_ppm": 1e-5, "trip_records": 0, "shift_frames": 0}))
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["paths"] = ["benchmarks/chip", "extra"]
    data["configs"] = [{"name": "ring8", "source": "test",
                        "file": "extra/configs/ring8.json", "reduced": [],
                        "why": "test"}]
    data["workloads"] = [{"name": "ring8.guard", "config": "ring8",
                          "traffic": "ring_guard", "chips": 1,
                          "why": "test"}]
    for m in data["end_to_end"]:
        if m["name"] == "node_periods_per_s":
            m["workloads"] = ["ring8.guard"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    spec = Spec(tmp_path)
    cell = spec.cell("ring8.guard")
    prog = program.Program(cell.config, cell.traffic, cell.kinds)
    assert prog.topo.num_nodes == 8 and prog.topo.num_edges == 16
    fab = reference.build_fabric(cell.config, cell.kinds)
    assert set(zip(fab.src.tolist(), fab.dst.tolist())) == set(
        zip(prog.topo.src.tolist(), prog.topo.dst.tolist()))
    line = harness.run_cell(spec, "ring8.guard", 2**31 + 5, 0.2, False,
                            time.perf_counter(), CPU)
    assert line["correct"] is True, line["checks"]
    assert {"trip_records", "shift_frames"} <= set(line["checks"])
    assert all(d["trips_held"] >= 1 for d in line["decisions"].values())
    # No harness file changed: the benchmark's own files are the repo's.
    for f in (ROOT / "benchmarks/chip").rglob("*.py"):
        if "__pycache__" not in f.parts:
            rel = f.relative_to(ROOT)
            assert (tmp_path / rel).read_bytes() == f.read_bytes()


FINC_HW = '''"""FINC/FDEC in the boards' own units: a gain in pulses per frame of
summed occupancy error and a pulse in ppm (``{"kind": "finc_hw",
"steps_per_frame": g, "step_ppm": s, "pulses_per_update": P}``), so
fs = s·1e-6 and kp = g·fs; each edge read as whole frames."""
import numpy as np


class FincHw:
    readout = "integer"

    def __init__(self, c, deg, dtype):
        self.fs = dtype(c["step_ppm"] * 1e-6)
        self.kp = dtype(c["steps_per_frame"] * c["step_ppm"] * 1e-6)
        self.budget = int(c["pulses_per_update"])
        self.dtype = dtype

    def init(self, shape):
        return {"c_est": np.zeros(shape, self.dtype)}

    def want(self, net, state):
        return (self.kp * net - state["c_est"]) / self.fs

    def step(self, net, nu_u, state):
        pulses = np.clip(np.rint(self.want(net, state)), -self.budget,
                         self.budget)
        c = state["c_est"] + pulses * self.fs
        return nu_u + c + nu_u * c, {"c_est": c}


def reference(c, deg, dtype):
    return FincHw(c, deg, dtype)


def program(c):
    from repro.core import ControllerConfig, hardware_gain
    fs = c["step_ppm"] * 1e-6
    return (ControllerConfig(kind="discrete", fs=fs,
                             kp=hardware_gain(c["steps_per_frame"], fs),
                             pulses_per_update=int(c["pulses_per_update"])),
            {"quantize_beta": True})
'''


def test_controller_kind_as_files(tmp_path, monkeypatch):
    """A FINC/FDEC kind in the boards' units, which no harness file knows,
    from a path of its own: it keeps state and reads whole frames, and the
    harness runs it (on segment-sum, which runs FINC/FDEC today) and holds
    its pulses through the replay with no harness edit."""
    from test_chipbench_discrete import DATA, on_segment_sum
    shutil.copytree(ROOT / "benchmarks/chip", tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    extra = tmp_path / "extra"
    for kind in ("kinds/controller", "configs", "traffic", "limits"):
        (extra / kind).mkdir(parents=True)
    (extra / "kinds/controller/finc_hw.py").write_text(FINC_HW)
    cfg = json.loads((DATA / "testbed_fc8_finc.json").read_text())
    cfg.update(name="testbed_hw", duration_s=0.1, controller={
        "kind": "finc_hw", "steps_per_frame": 0.2, "step_ppm": 0.1,
        "pulses_per_update": 50})
    (extra / "configs/testbed_hw.json").write_text(json.dumps(cfg))
    mix = json.loads((DATA / "finc_mc.json").read_text())
    mix["draws"] = 16
    (extra / "traffic/hw_mc.json").write_text(json.dumps(mix))
    shutil.copy(DATA / "finc_limits_2000.json",
                extra / "limits/testbed_hw.mc.json")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["paths"] = ["benchmarks/chip", "extra"]
    data["configs"] = [{"name": "testbed_hw", "source": "test",
                        "file": "extra/configs/testbed_hw.json",
                        "reduced": [], "why": "test"}]
    data["workloads"] = [{"name": "testbed_hw.mc", "config": "testbed_hw",
                          "traffic": "hw_mc", "chips": 1, "why": "test"}]
    for m in data["end_to_end"]:
        if m["name"] == "draws_per_s":
            m["workloads"] = ["testbed_hw.mc"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    on_segment_sum(monkeypatch)
    spec = Spec(tmp_path)
    cell = spec.cell("testbed_hw.mc")
    prog = program.Program(cell.config, cell.traffic, cell.kinds)
    assert prog.ctrl.kind == "discrete" and prog.cfg.quantize_beta
    line = harness.run_cell(spec, "testbed_hw.mc", 2**31 + 9, 0.2, False,
                            time.perf_counter(), CPU)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["pulse_records"]["value"] == 0
    assert all(d["records_held"] > d["records_replayed"]
               for d in line["decisions"].values())
    for f in (ROOT / "benchmarks/chip").rglob("*.py"):
        if "__pycache__" not in f.parts:
            rel = f.relative_to(ROOT)
            assert (tmp_path / rel).read_bytes() == f.read_bytes()


# sha256 of the reference's outputs at a small seeded size, computed
# before the kinds moved into files (the discrete kind's when it came).
PINNED = {
    ("discrete", "float32"):
        "5700ff1cecf3b95f5d4d8e524f49c7022094e7944af84562306d2e3f424a9802",
    ("discrete", "high"):
        "7be7ee70bea9efbbbe6a68d30f7ed2c1dd41e34d6414870a793b7697d5e297dd",
    ("fully_connected", "float32"):
        "54b73826b3138fdbd36b76e76ade7fef4061ab64ee5802e56abd87d4a8d3f178",
    ("fully_connected", "high"):
        "498e81940cb08920f229c5a8e960c2b841972a2b3bd41690bc355187c31f9204",
    ("torus3d", "float32"):
        "84472422c3c90a5bca912420184d7075f7d6d4ea2eb3950e4f25dc6920b35fe4",
    ("torus3d", "high"):
        "3383df8b1bc11a1da03e981d299ee10eac0eaddaefb913c27940863214b3714a",
}


def _small(kind):
    bench = ROOT / "benchmarks/chip"
    if kind == "discrete":
        from test_chipbench_discrete import DATA
        cfg = json.loads((DATA / "testbed_fc8_finc.json").read_text())
        cfg["duration_s"] = 0.1
        mix = json.loads((DATA / "finc_mc.json").read_text())
        mix.update(draws=4, record_every=200)
    elif kind == "fully_connected":
        cfg = json.loads((bench / "configs/testbed_fc8.json").read_text())
        mix = json.loads((bench / "traffic/splice_mc.json").read_text())
        mix["draws"] = 4
    else:
        cfg = json.loads((bench / "configs/torus3d_22.json").read_text())
        cfg["topology"] = {"kind": "torus3d", "k": 4}
        mix = json.loads((bench / "traffic/free.json").read_text())
        mix["draws"] = 3
    return cfg, mix


@pytest.mark.parametrize("kind,precision", sorted(PINNED))
def test_reference_outputs_unchanged(kind, precision):
    cfg, mix = _small(kind)
    kinds = Spec(ROOT).kinds(cfg, mix)
    out = reference.simulate(
        cfg, mix, kinds, generator.draws(cfg, mix, kinds, 2**31 + 11, 3),
        precision=precision)
    h = hashlib.sha256()
    for k in sorted(out):
        h.update(k.encode())
        h.update(np.ascontiguousarray(out[k]).tobytes())
    assert h.hexdigest() == PINNED[(kind, precision)]
