"""FINC/FDEC controller (arXiv:2503.05033 §4.3, §5.7): a node moves its
oscillator only in pulses of ``fs`` (relative frequency), at most
``pulses_per_update`` a control period, and keeps their sum ``c_est``:

    err_i   = Σ_{e→i} (read(β_e) − β_off)
    pulses  = clip(rint((kp·err_i − c_est)/fs), ±pulses_per_update)
    c_est  += pulses·fs
    ν_i     = ν_u + c_est + ν_u·c_est

``read`` is the buffer readout: ``"integer"`` rounds each edge's
occupancy to the nearest integer before the node sum (the hardware reads
whole frames), ``"continuous"`` takes it as it is.  ``c_est`` starts at 0.

``{"kind": "discrete", "kp": ..., "fs": ..., "pulses_per_update": ...,
"beta_off_frames": ..., "readout": "integer"}``

The kind states the rule alone; ``compare.PulseReplay`` holds its
decisions, with integer readout, and owns their float32 analysis.
"""
import numpy as np


class Discrete:
    """The per-period update in ``dtype``; its state is ``{"c_est"}``."""

    def __init__(self, c: dict, deg, dtype):
        if c["readout"] not in ("integer", "continuous"):
            raise ValueError(f"unknown readout {c['readout']!r}")
        self.readout = c["readout"]
        self.kp = dtype(c["kp"])
        self.fs = dtype(c["fs"])
        self.budget = int(c["pulses_per_update"])
        self.boff = dtype(c.get("beta_off_frames", 0.0))
        self.deg = deg
        self.dtype = dtype

    def init(self, shape) -> dict:
        return {"c_est": np.zeros(shape, self.dtype)}

    def want(self, net, state):
        """The pulses the rule asks for, before rounding and the budget."""
        return (self.kp * (net - self.boff * self.deg)
                - state["c_est"]) / self.fs

    def step(self, net, nu_u, state):
        pulses = np.clip(np.rint(self.want(net, state)), -self.budget,
                         self.budget)
        c = state["c_est"] + pulses * self.fs
        return nu_u + c + nu_u * c, {"c_est": c}


def reference(c: dict, deg, dtype):
    return Discrete(c, deg, dtype)


def program(c: dict):
    from repro.core import ControllerConfig
    ctrl = ControllerConfig(kind="discrete", kp=c["kp"], fs=c["fs"],
                            pulses_per_update=int(c["pulses_per_update"]),
                            beta_off=c["beta_off_frames"])
    return ctrl, {"quantize_beta": c["readout"] == "integer"}
