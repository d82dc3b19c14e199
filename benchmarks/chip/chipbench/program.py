"""The system under test, driven as a user drives it.

The window calls ``repro.scenarios.run_scenario`` with
``EngineOptions(engine="auto")``, so dispatch stays free and a dispatch
change shows in the numbers.  Topology, links, controller and events are
built once, from the configuration and traffic files; each call gets its
own oscillator draws.  Only this module imports the program.
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

    def __init__(self, config: dict, traffic: dict):
        from repro.core import (ControllerConfig, SimConfig, fully_connected,
                                make_links, torus3d)
        from repro.kernels import EngineOptions
        from repro.scenarios import (LatencyStep, Scenario, edges_between,
                                     run_scenario)
        from repro.telemetry import Telemetry

        self._run = run_scenario
        self._telemetry = Telemetry
        t = config["topology"]
        if t["kind"] == "fully_connected":
            self.topo = fully_connected(int(t["nodes"]))
        elif t["kind"] == "torus3d":
            self.topo = torus3d(int(t["k"]))
        else:
            raise ValueError(f"unknown topology kind {t['kind']!r}")
        phys = dict(omega_nom=config["omega_nom_hz"],
                    pipe_frames=config["pipe_frames"],
                    velocity=config["signal_velocity_m_per_s"])
        self.links = make_links(self.topo, cable_m=config["cable_m"],
                                beta0=config["beta0_frames"], **phys)
        c = config["controller"]
        if c["kind"] != "proportional":
            raise ValueError(f"unknown controller kind {c['kind']!r}")
        self.ctrl = ControllerConfig(kp=c["kp"], beta_off=c["beta_off_frames"])
        periods = int(round(config["duration_s"] / config["dt_s"]))
        self.cfg = SimConfig(omega_nom=config["omega_nom_hz"],
                             dt=config["dt_s"], steps=periods,
                             record_every=int(traffic["record_every"]))
        events = []
        for ev in traffic.get("events", []):
            if ev["kind"] != "latency_step":
                raise ValueError(f"unknown event kind {ev['kind']!r}")
            events.append(LatencyStep(
                t=ev["t_s"], edges=edges_between(self.topo, *ev["link"]),
                cable_m=ev["cable_m"],
                reestablish=bool(ev.get("reestablish", False))))
        self.scenario = Scenario(events=tuple(events))
        self.options = EngineOptions(engine="auto")
        self.tel = traffic["telemetry"]

    def call(self, ppm: np.ndarray, trace=None):
        """One scenario run; its result arrays are on the host."""
        tel = self._telemetry(beta=self.tel["beta"],
                              watermarks=self.tel["watermarks"],
                              trace=trace if trace is not None else False)
        return self._run(self.topo, self.links, self.ctrl, ppm,
                         self.scenario, self.cfg, options=self.options,
                         telemetry=tel)

    @staticmethod
    def answer(res) -> dict:
        """What the comparison reads from one result, as host copies:
        the records and the watermarks."""
        wm = res.watermarks
        return {"freq_ppm": np.array(res.freq_ppm),
                "beta": np.array(res.beta),
                "beta_abs_max": np.array(wm.beta_abs_max),
                "nu_min_ppm": np.array(wm.nu_min_ppm),
                "nu_max_ppm": np.array(wm.nu_max_ppm)}
