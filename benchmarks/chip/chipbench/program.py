"""The system under test, driven as a user drives it.

The window calls ``repro.scenarios.run_scenario`` with
``EngineOptions(engine="auto")``, so dispatch stays free and a dispatch
change shows in the numbers.  Topology, links, controller and events are
built once, from the configuration and traffic files, by the program
halves of their kind files (``spec.Kinds``); the controller kind also
names the ``SimConfig`` fields it needs (integer readout is
``quantize_beta``), and this module names none.  A mix's ``guard`` entry
(``{"margin_frames": m}``) turns on the reframing guard,
``ReframePolicy(D, margin=m)``, at the configuration's
``elastic_buffer_depth`` D.  Each call gets its own oscillator
draws.  Only this module, and the program halves of the kind files it
calls, import the program.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np


def add_to_path(root: Path) -> None:
    """Put the checkout's ``src`` first on the import path."""
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class Program:
    """One configuration under one traffic mix, ready to call."""

    def __init__(self, config: dict, traffic: dict, kinds):
        from repro.core import ReframePolicy, SimConfig, make_links
        from repro.kernels import EngineOptions
        from repro.scenarios import Scenario, run_scenario
        from repro.telemetry import Telemetry

        self._run = run_scenario
        self._telemetry = Telemetry
        self.topo = kinds.topology.program(config["topology"])
        phys = dict(omega_nom=config["omega_nom_hz"],
                    pipe_frames=config["pipe_frames"],
                    velocity=config["signal_velocity_m_per_s"])
        self.links = make_links(self.topo, cable_m=config["cable_m"],
                                beta0=config["beta0_frames"], **phys)
        self.ctrl, sim = kinds.controller.program(config["controller"])
        periods = int(round(config["duration_s"] / config["dt_s"]))
        self.cfg = SimConfig(omega_nom=config["omega_nom_hz"],
                             dt=config["dt_s"], steps=periods,
                             record_every=int(traffic["record_every"]),
                             **sim)
        self.scenario = Scenario(events=tuple(
            kinds.event(ev).program(ev, self.topo, config)
            for ev in traffic.get("events", [])))
        self.options = EngineOptions(engine="auto")
        self.tel = traffic["telemetry"]
        g = traffic.get("guard")
        self.guard = (ReframePolicy(depth=int(config["elastic_buffer_depth"]),
                                    margin=float(g["margin_frames"]))
                      if g else False)

    def call(self, ppm: np.ndarray, trace=None):
        """One scenario run; its result arrays are on the host."""
        tel = self._telemetry(beta=self.tel["beta"],
                              watermarks=self.tel["watermarks"],
                              trace=trace if trace is not None else False,
                              guard=self.guard)
        return self._run(self.topo, self.links, self.ctrl, ppm,
                         self.scenario, self.cfg, options=self.options,
                         telemetry=tel)

    @staticmethod
    def answer(res) -> dict:
        """What the comparison reads from one result, as host copies:
        the records and the watermarks; where the guard rotated, each
        rotation's record and (B, E) integer shift, and the (E, 2)
        (src, dst) pairs the shifts index."""
        wm = res.watermarks
        out = {"freq_ppm": np.array(res.freq_ppm),
               "beta": np.array(res.beta),
               "beta_abs_max": np.array(wm.beta_abs_max),
               "nu_min_ppm": np.array(wm.nu_min_ppm),
               "nu_max_ppm": np.array(wm.nu_max_ppm),
               "reframes": [(int(r.record),
                             np.atleast_2d(np.array(r.shift, np.int64)))
                            for r in res.reframes]}
        if res.reframes:
            out["edges"] = np.stack([np.asarray(res.topo.src, np.int64),
                                     np.asarray(res.topo.dst, np.int64)],
                                    axis=1)
        return out
