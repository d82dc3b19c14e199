#!/usr/bin/env python3
"""Run the bittide scenario engines once on one TPU and check what comes out.

    python chip_smoke.py

The quickest proof that the system still starts on the chip.  It drives
``repro.scenarios.run_scenario`` — the entry point users call — through
the Pallas lanes dispatch picks at the sizes users run, and holds every
kernel phase to the segment-sum oracle, run in the same process on the
host CPU device, at the cross-engine tolerances of
``tests/engine_harness.py``:

``testbed``
    The paper's 8-node fully connected testbed, B=8 oscillator draws at
    ±8 ppm, with the §5.6 fibre splice (link (0, 2) goes from 2 m of
    copper to 1 km of fibre each way, buffers re-established), on the lane
    ``engine="auto"`` picks (fused) with β and watermarks on.  Checks the
    ≈1231-frame round-trip latency shift of the spliced link.
``torus-auto`` / ``torus-sparse`` (and ``-guard``)
    The Fig-18 torus3d(22), 10,648 nodes, B=8 draws at ±8 ppm, 200
    periods under the engine parity matrix's controller (kp=2e-9,
    dt=1 ms; not the 10× stiffer one of ``examples/scale_torus.py``,
    where float32 rounding alone exceeds the tolerances below), with β
    and watermarks on: once on the lane ``engine="auto"`` picks (tiled),
    once on ``engine="sparse"``, each without and with the in-kernel
    reframing guard (32-deep buffers).  Each matches segment-sum up to
    its first splice; the two guard runs splice the same rotations at
    the same records, and the sparse one matches the tiled one's ν and β
    at every record to the end.

Each phase prints one JSON line: lane and panel width, shapes, the
largest error of each check beside its tolerance, compile seconds, and
the wall seconds of a second, warm run (``run_scenario`` returns host
arrays, so the time covers the device work) with the part of it spent
in engine launches (the run trace's chunk spans).  The times are
information, not a benchmark.  The last line is ``{"ok": true, "device": {...}}``.

Exits non-zero, without that line, when JAX finds no TPU, when the
``repro`` sources next to this file are missing, or when any check fails.
"""
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# The oracle runs on the host CPU device: keep that backend available
# where the platform list is pinned to the TPU.
_plat = os.environ.get("JAX_PLATFORMS", "")
if _plat and "tpu" in _plat.split(",") and "cpu" not in _plat.split(","):
    os.environ["JAX_PLATFORMS"] = _plat + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

TORUS_K = 22
DRAWS = 8


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"no TPU found (JAX's first device is {dev.platform!r}); "
              "this script runs only on a TPU")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


class _CompileClock:
    """Seconds JAX spent in backend compiles while the clock is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if self._open and event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        self.seconds, self._open = 0.0, True
        return self

    def __exit__(self, *exc):
        self._open = False


def _state(nodes: int) -> list:
    """The kernels' padded (B, N) state shape."""
    return [-(-DRAWS // 8) * 8, -(-nodes // 128) * 128]


def _max_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        _fail(f"shape {got.shape} != reference shape {ref.shape}")
    if not np.isfinite(got).all():
        _fail("non-finite values in a kernel result")
    return float(np.abs(got - ref).max()) if got.size else 0.0


def _check(name: str, err: float, tol: float, line: dict) -> None:
    line[name] = {"max_err": err, "tol": tol}
    if not err <= tol:
        print(json.dumps(line), flush=True)
        _fail(f"{line['phase']}: {name} error {err:.3g} exceeds {tol:.3g}")


def _beta_tol(ref, operands: float = 0.0) -> float:
    """The cross-engine β tolerance at the reference's scale, or at the
    scale of the terms β is summed from where that is larger."""
    from engine_harness import BETA_ATOL_CROSS_FRAMES, BETA_RTOL_CROSS
    return BETA_ATOL_CROSS_FRAMES + BETA_RTOL_CROSS * max(
        float(np.abs(np.asarray(ref)).max(initial=0.0)), operands)


def _beta_operands(res, topo) -> float:
    """Scale (frames) of the terms the kernels sum into a node's β: up to
    in-degree many centred ψ terms, |ψ − row mean| as at the end of the
    run, and the λeff fold that cancels them."""
    psi = np.asarray(res.psi, np.float64)
    spread = float(np.abs(psi - psi.mean(axis=-1, keepdims=True)).max())
    return float(np.asarray(topo.in_degree).max()) * spread


def _run(clock, *args, telemetry, **kw):
    """Cold run (compile seconds), then a timed, traced warm run of the
    same call; the trace's chunk spans are the engine launches up to
    their results on the host, the rest of the wall time is host work."""
    import dataclasses
    from repro.scenarios import run_scenario
    with clock:
        run_scenario(*args, telemetry=telemetry, **kw)
    t0 = time.perf_counter()
    res = run_scenario(*args, telemetry=dataclasses.replace(
        telemetry, trace=True), **kw)
    wall = time.perf_counter() - t0
    launch = sum(e.dur for e in res.trace.by_kind("chunk"))
    return res, {"compile_s": clock.seconds, "steady_s": wall,
                 "launch_s": launch}


def _oracle(*args, **kw):
    """The segment-sum lane on the host CPU device: its result and its
    per-edge β folded to the kernels' per-node net occupancy."""
    from repro.core.reframing import node_net_occupancy
    from repro.kernels import EngineOptions
    from repro.scenarios import run_scenario
    with jax.default_device(jax.devices("cpu")[0]):
        res = run_scenario(*args, options=EngineOptions(engine="segment-sum"),
                           **kw)
    return res, node_net_occupancy(args[0], res.beta, None)


def testbed(clock) -> dict:
    from engine_harness import FREQ_ATOL_PPM
    from repro.core import (ControllerConfig, OscillatorSpec, SimConfig,
                            fully_connected, make_links)
    from repro.kernels import EngineOptions
    from repro.scenarios import (LatencyStep, Scenario, edges_between)
    from repro.telemetry import Telemetry

    topo = fully_connected(8)
    links = make_links(topo, cable_m=2.0)
    ppm = np.stack([OscillatorSpec(initial_ppm=8.0, seed=s).sample(8)
                    for s in range(DRAWS)]).astype(np.float32)
    # The engine parity matrix's controller (tests/engine_harness.py):
    # at the example's 10× gain, float32 rounding alone puts the lanes
    # a few 1e-6 ppm apart.
    cfg = SimConfig(dt=1e-3, steps=400, record_every=20)
    swap = edges_between(topo, 0, 2)
    sc = Scenario(events=(LatencyStep(t=0.2, edges=swap, cable_m=1000.0,
                                      reestablish=True),))
    args = (topo, links, ControllerConfig(kp=2e-9), ppm, sc, cfg)
    tel = Telemetry(beta=True, watermarks=True)
    res, times = _run(clock, *args, options=EngineOptions(engine="auto"),
                      telemetry=tel)
    ref, ref_beta = _oracle(*args, telemetry=Telemetry(beta=True,
                                                       watermarks=True))
    e = swap[0]
    shift = int(res.rtt(1)[e] - res.rtt(0)[e])
    line = {"phase": "testbed", "lane": res.engine, "tile": res.tile_j,
            "draws": DRAWS, "nodes": topo.num_nodes,
            "state": _state(topo.num_nodes),
            "records": int(res.freq_ppm.shape[1]),
            "rtt_shift_frames": shift, **times}
    _check("freq_ppm", _max_err(res.freq_ppm, ref.freq_ppm), FREQ_ATOL_PPM,
           line)
    _check("beta_frames", _max_err(res.beta, ref_beta), _beta_tol(ref_beta),
           line)
    _check("beta_peak_frames", _max_err(res.watermarks.beta_abs_max,
                                        ref.watermarks.beta_abs_max),
           _beta_tol(ref.watermarks.beta_abs_max), line)
    _check("rtt_frames", _max_err(res.rtt(1), ref.rtt(1)), 0.0, line)
    _check("rtt_shift_vs_1231", abs(shift - 1231), 3, line)
    print(json.dumps(line), flush=True)
    return line


def torus(clock, k: int = TORUS_K) -> list:
    from engine_harness import FREQ_ATOL_PPM
    from repro.core import (ControllerConfig, ReframePolicy, SimConfig,
                            make_links, torus3d)
    from repro.core.envelopes import reframe_guard_margin
    from repro.kernels import EngineOptions
    from repro.scenarios import Scenario
    from repro.telemetry import Telemetry

    topo = torus3d(k)
    links = make_links(topo, cable_m=2.0)
    ppm = np.random.default_rng(0).uniform(
        -8, 8, (DRAWS, topo.num_nodes)).astype(np.float32)
    # The engine parity matrix's controller, as for the testbed: at the
    # gain of examples/scale_torus.py (kp=2e-8, dt=5e-3) float32 rounding
    # alone puts the lanes past 1e-6 ppm in the first record, and further
    # with every record (ROADMAP queue 1).
    kp, dt, rec = 2e-9, 1e-3, 20
    cfg = SimConfig(dt=dt, steps=200, record_every=rec)
    # The default margin solves the Laplacian spectrum of every draw —
    # minutes at 10^4 nodes.  Every 3-D torus is 6-regular with the same
    # λ_max and per-node slack terms, so a small one is a faithful proxy
    # (as in examples/scale_torus.py).
    margin = reframe_guard_margin(torus3d(6), kp, dt, rec, nu_bound=8e-6,
                                  lat_frames_max=2.0)
    policy = ReframePolicy(depth=32, margin=margin)
    args = (topo, links, ControllerConfig(kp=kp), ppm, Scenario(events=()),
            cfg)
    ref, ref_beta = _oracle(*args, telemetry=Telemetry(beta=True,
                                                       watermarks=True))
    lines, trips = [], {}
    for lane in ("auto", "sparse"):
        for guard in (False, True):
            tel = Telemetry(beta=True, watermarks=True,
                            guard=policy if guard else False)
            res, times = _run(clock, *args,
                              options=EngineOptions(engine=lane),
                              telemetry=tel)
            # Segment-sum runs no splices: compare up to the first one.
            r0 = (res.reframes[0].record if res.reframes
                  else res.freq_ppm.shape[1])
            line = {"phase": f"torus-{lane}" + ("-guard" if guard else ""),
                    "lane": res.engine, "tile": res.tile_j, "draws": DRAWS,
                    "nodes": topo.num_nodes,
                    "state": _state(topo.num_nodes),
                    "records": int(res.freq_ppm.shape[1]),
                    "oracle_records": int(r0), "reframes": len(res.reframes),
                    "launches": res.num_launches, **times}
            _check("freq_ppm", _max_err(res.freq_ppm[:, :r0],
                                        ref.freq_ppm[:, :r0]),
                   FREQ_ATOL_PPM, line)
            _check("beta_frames", _max_err(res.beta[:, :r0],
                                           ref_beta[:, :r0]),
                   _beta_tol(ref_beta[:, :r0]), line)
            if not guard:
                _check("beta_peak_frames",
                       _max_err(res.watermarks.beta_abs_max,
                                ref.watermarks.beta_abs_max),
                       _beta_tol(ref.watermarks.beta_abs_max), line)
            else:
                # The harness guard lane's contract: every kernel lane
                # trips at the same records and splices the same shifts.
                trips[lane] = [(r.record, np.asarray(r.shift).tolist())
                               for r in res.reframes]
                if lane == "auto":
                    guarded = res
                else:
                    _check("reframes_unlike_auto",
                           float(trips[lane] != trips["auto"]), 0.0, line)
                    # After the splices, against the tiled guard run.
                    # The splices keep β small while the centred-ψ and
                    # λeff terms it is summed from keep growing, so
                    # float32 rounding of β follows those terms' scale,
                    # not β's own.
                    _check("freq_ppm_vs_auto",
                           _max_err(res.freq_ppm, guarded.freq_ppm),
                           FREQ_ATOL_PPM, line)
                    _check("beta_frames_vs_auto",
                           _max_err(res.beta, guarded.beta),
                           _beta_tol(guarded.beta,
                                     _beta_operands(guarded, topo)),
                           line)
            lines.append(line)
            print(json.dumps(line), flush=True)
    return lines


def main() -> None:
    info = _device()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    from repro.compile_cache import enable_compile_cache
    cache = Path(enable_compile_cache())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(json.dumps({"device": info, "jax": jax.__version__,
                      "compile_cache": str(cache),
                      "cache_entries_at_start": warm}), flush=True)
    clock = _CompileClock()
    testbed(clock)
    torus(clock)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
