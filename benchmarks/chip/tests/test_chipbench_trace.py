"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: exact on a hand-made trace, and sound on small traces recorded on
a TPU v5e (``data/<name>_trace.json``: one profiled call of
``testbed.splice_mc`` and of ``torus22.free``, as
``chipbench.trace.extract`` reads it)."""
import json
from pathlib import Path

import pytest

import _chipbench_path  # noqa: F401
from chipbench import trace
from chipbench.spec import Spec

KERNELS = {"fused": ["_fused_kernel"], "tiled": ["_tiled_kernel"]}


def test_hand_made_trace():
    ms = 1_000_000
    t = {"host": [("bench_call", 0, 100 * ms),
                  ("chunk:fused", 10 * ms, 30 * ms),
                  ("chunk:fused", 60 * ms, 30 * ms)],
         "device": {"/device:TPU:0": [
             ("copy.1", 5 * ms, 5 * ms),
             ("_fused_kernel.3", 20 * ms, 10 * ms),
             ("fusion.2", 25 * ms, 10 * ms),         # overlaps the kernel
             ("_fused_kernel.3", 70 * ms, 10 * ms),
             ("copy.9", 150 * ms, 5 * ms)]}}         # after the window
    s = trace.reduce(t, KERNELS)
    assert s.calls == 1
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.030)           # 5 + 15 + 10 ms
    assert s.kernel_s == {"fused": pytest.approx(0.020), "tiled": 0.0}
    assert s.device_ops[0] == ["_fused_kernel.3", pytest.approx(0.020)]
    # Gaps: 0-5 (call only), 10-20 (in a chunk), 35-70 (crosses chunks,
    # middle 52.5 ms is in the call only), 80-100 (in the second chunk
    # until 90 ms, middle 90 ms sits on its end).
    assert [g[1] for g in s.idle_gaps] == pytest.approx(
        [0.035, 0.020, 0.010, 0.005])
    assert [g[0] for g in s.idle_gaps] == [
        "bench_call", "chunk:fused", "chunk:fused", "bench_call"]


def test_no_device_op_in_the_window():
    t = {"host": [("bench_call", 0, 10)],
         "device": {"/device:TPU:0": [("copy", 20, 5)]}}
    with pytest.raises(ValueError):
        trace.reduce(t, KERNELS)


def test_short_name():
    op = ('%_fused_engine.1 = (f32[8,128]{1,0}) custom-call(f32[8,128] %a), '
          'custom_call_target="tpu_custom_call", backend_config="x"')
    assert trace.short_name(op) == "%_fused_engine.1 [tpu_custom_call]"
    assert trace.short_name("%copy.2 = f32[8,1] copy(%x)") == "%copy.2"


@pytest.mark.parametrize("name,lane,kernel_ms", [
    ("testbed", "fused", 1.098497), ("torus", "tiled", 131.075728)])
def test_recorded_trace(name, lane, kernel_ms):
    rec = json.loads((Path(__file__).parent / f"data/{name}_trace.json")
                     .read_text())
    lanes = Spec(_chipbench_path.ROOT).lanes()
    s = trace.reduce(rec, {n: m.TRACE_NAMES for n, m in lanes.items()})
    assert s.calls == 1
    assert 0 < s.busy_s < s.window_s
    # The Pallas period kernel is the one custom call of the engine.
    assert s.kernel_s[lane] == pytest.approx(kernel_ms * 1e-3)
    assert s.kernel_s[lane] <= s.busy_s
    assert s.device_ops[0][0] == "%_fused_engine.1 [tpu_custom_call]"
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10
    assert all(g[1] > 0 for g in s.idle_gaps)
    assert {g[0] for g in s.idle_gaps} <= {
        n for n, _, _ in rec["host"]} | {"between calls"}
