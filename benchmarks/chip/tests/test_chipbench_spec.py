"""Every cell resolves by name; a cell added as files alone loads; the
measurement path fails without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import _chipbench_path  # noqa: F401
from chipbench import harness
from chipbench.spec import Spec

ROOT = _chipbench_path.ROOT
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_files_resolve(cell):
    spec = Spec(ROOT)
    c = spec.cell(cell)
    assert c.config["name"] in {x["name"] for x in spec.data["configs"]}
    assert c.traffic["draws"] > 0 and c.limits
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(spec.reader(m["name"]).read)
    for entry in spec.data["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["reduced"] == entry["reduced"]


def test_cell_in_a_temporary_directory(tmp_path):
    """A new configuration, mix, metric and limits need files and
    BENCHMARK.json entries only."""
    bench = tmp_path / "extra"
    for kind in ("configs", "traffic", "limits", "metrics"):
        (bench / kind).mkdir(parents=True)
    cfg = json.loads((ROOT / "benchmarks/chip/configs/testbed_fc8.json")
                     .read_text())
    cfg.update(name="ring_like", topology={"kind": "fully_connected",
                                           "nodes": 4})
    (bench / "configs/ring_like.json").write_text(json.dumps(cfg))
    (bench / "traffic/tiny.json").write_text(json.dumps(
        {"draws": 2, "record_every": 20, "events": [], "check_calls": 1,
         "telemetry": {"beta": True, "watermarks": True}}))
    (bench / "limits/ring_like.tiny.json").write_text(json.dumps(
        {"freq_ppm": 1e-5, "beta_frames": 1e-2, "beta_peak_frames": 1e-2,
         "nu_extremes_ppm": 1e-5}))
    (bench / "metrics/calls_seen.py").write_text(
        "def read(r):\n    return float(len(r.calls))\n")
    data = _bench()
    data["paths"] = ["extra"]
    data["configs"] = [{"name": "ring_like", "source": "test",
                        "file": "extra/configs/ring_like.json",
                        "reduced": [], "why": "test"}]
    data["workloads"] = [{"name": "ring_like.tiny", "config": "ring_like",
                          "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in data["end_to_end"]:
        if m["name"] == "node_periods_per_s":
            m["workloads"] = ["ring_like.tiny"]
    data["per_layer"] = [{"name": "calls_seen", "unit": "calls",
                          "better": "higher", "source": "host_clock",
                          "layer": "test", "moves": "node_periods_per_s"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    c = Spec(tmp_path).cell("ring_like.tiny")
    assert c.config["topology"]["nodes"] == 4
    assert c.traffic["draws"] == 2
    assert [m["name"] for m in c.per_layer] == ["calls_seen"]
    mod = Spec(tmp_path).module("metrics", "calls_seen")
    assert mod.read(type("R", (), {"calls": [1, 2]})()) == 2.0
    # And it runs: the harness's own run of the new cell, on the CPU.
    line = harness.run_cell(Spec(tmp_path), "ring_like.tiny", 7, 0.2, False,
                            time.perf_counter(), CPU)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"node_periods_per_s", "setup_s"}


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "testbed.splice_mc", "--seed", "3", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_benchmark_files_alone_no_result(tmp_path):
    """Without the program next to it, the benchmark fails, printing no
    result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks/chip", tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()
