"""Proportional controller: ν = ν_u + c + ν_u·c with c = kp·err, where
err_i = Σ_{e→i} (β_e − β_off), read from continuous occupancies
(``{"kind": "proportional", "kp": ..., "beta_off_frames": ...}``)."""


class Proportional:
    """The per-period update in ``dtype``; it keeps no state."""

    readout = "continuous"

    def __init__(self, c: dict, deg, dtype):
        self.kp = dtype(c["kp"])
        self.boff = dtype(c.get("beta_off_frames", 0.0))
        self.deg = deg

    def init(self, shape) -> dict:
        return {}

    def step(self, net, nu_u, state):
        err = net - self.boff * self.deg
        rel = self.kp * err
        return nu_u + rel + nu_u * rel, state


def reference(c: dict, deg, dtype):
    return Proportional(c, deg, dtype)


def program(c: dict):
    from repro.core import ControllerConfig
    return ControllerConfig(kp=c["kp"], beta_off=c["beta_off_frames"]), {}
