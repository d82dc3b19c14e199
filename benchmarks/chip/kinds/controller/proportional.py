"""Proportional controller: ν = ν_u + c + ν_u·c with c = kp·err, where
err_i = Σ_{e→i} (β_e − β_off) (``{"kind": "proportional", "kp": ...,
"beta_off_frames": ...}``)."""


def reference(c: dict, deg, dtype):
    """The per-period update in ``dtype``: ``step(net, nu_u, nu)`` from the
    per-node net occupancy Σ_{e→i} β_e."""
    kp = dtype(c["kp"])
    boff = dtype(c.get("beta_off_frames", 0.0))

    def step(net, nu_u, nu):
        err = net - boff * deg
        rel = kp * err
        return nu_u + rel + nu_u * rel

    return step


def program(c: dict):
    from repro.core import ControllerConfig
    return ControllerConfig(kp=c["kp"], beta_off=c["beta_off_frames"])
