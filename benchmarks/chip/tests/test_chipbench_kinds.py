"""Fabric, controller and event kinds are files: a new kind needs files
and ``BENCHMARK.json`` entries only, and the kinds in use give the
reference the outputs it gave before they moved into files."""
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


# sha256 of the reference's outputs at a small seeded size, computed
# before the kinds moved into files.
PINNED = {
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
    if kind == "fully_connected":
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
