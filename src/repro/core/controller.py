"""bittide clock controllers (paper §2 and §4.3).

Units
-----
``kp`` is the *effective* proportional gain in relative-frequency per frame
of occupancy error, matching the absolute units of the paper's Fig. 15
caption ("proportional gain 2e-8").  The hardware text quotes gains in units
of FINC/FDEC steps per frame (k_p = 0.25 / 25); the conversion is
``kp = kp_hw * fs_hw`` — use :func:`hardware_gain`.

``fs`` is the FINC/FDEC step size as a relative frequency (0.01 ppm = 1e-8).

Controller kinds
----------------
- ``proportional`` — eq. (1) of the paper, continuous actuation (the
  analysis model of [10]).
- ``discrete`` — the hardware-faithful actuator of §4.3: the controller can
  only emit FINC/FDEC pulses, tracked by the accumulated estimate
  ``c_est = fs * Σ c_inc``; at most ``pulses_per_update`` pulses are issued
  per control period (the boards accept one pulse per µs).
- ``pi`` — proportional–integral variant (beyond-paper; the integral term
  removes the steady-state buffer offset that pure-P control leaves, cf. the
  consensus literature the paper cites [33]).

Which engine runs which: the segment-sum simulator
(:mod:`repro.core.frame_model`) runs all three kinds, with continuous or
integer (``SimConfig.quantize_beta``) buffer readout.  Every Pallas lane
runs ``proportional`` with continuous readout; the fused lane also runs
``discrete`` and integer readout (``repro.kernels.period``); ``pi`` runs
on segment-sum alone (``repro.scenarios.run_scenario`` refuses the rest).

Gain sweeps
-----------
``kp`` and ``beta_off`` are *traced* through both simulation engines: they
never key a compile, and in the batched ensemble lanes they may be arrays
with one entry per draw (Fig-15-style gain sweeps run as ONE compiled
batched kernel).  ``ControllerConfig.static_key()`` is the hashable copy
the jit caches key on — identical for every gain value.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ControllerConfig", "hardware_gain", "controller_init",
           "controller_step", "holdover_freeze"]


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    """A controller kind and its constants (module docstring: which
    engine runs which kind and readout)."""
    kind: str = "proportional"  # proportional | discrete | pi
    kp: float = 2e-10           # relative-frequency per frame of occupancy error
    ki: float = 0.0             # integral gain (pi only), per frame per control period
    beta_off: float = 0.0       # occupancy setpoint, frames (normalized; DDC midpoint = 0)
    fs: float = 1e-8            # FINC/FDEC step size (discrete only)
    pulses_per_update: int = 64 # max pulses per control period (1 MHz pulse rate * dt)

    def __post_init__(self):
        if self.kind not in ("proportional", "discrete", "pi"):
            raise ValueError(f"unknown controller kind {self.kind!r}")
        # kp / beta_off may be per-draw arrays (batched gain sweeps).
        if np.any(np.asarray(self.kp) < 0) or self.fs <= 0:
            raise ValueError("kp must be >= 0 and fs > 0")

    def static_key(self) -> "ControllerConfig":
        """Hashable copy with the traced gains zeroed.

        ``kp`` and ``beta_off`` are traced runtime values in both engines;
        this is the config the jit caches key on, so sweeping gains (scalar
        or per-draw arrays) can never trigger a recompile.
        """
        return dataclasses.replace(self, kp=0.0, beta_off=0.0)


def hardware_gain(kp_hw: float, fs: float) -> float:
    """Convert the paper's hardware gain (steps/frame) to effective kp."""
    return kp_hw * fs


def controller_init(cfg: ControllerConfig, num_nodes: int):
    """Initial controller state: (c_est for discrete, integral for pi)."""
    del cfg
    zeros = jnp.zeros((num_nodes,), jnp.float32)
    return {"c_est": zeros, "integ": zeros}


def controller_step(cfg: ControllerConfig, state, agg_err, kp=None):
    """One control update.

    Args:
      cfg: controller configuration.
      state: dict carry from :func:`controller_init`.
      agg_err: (N,) summed occupancy error Σ_{j→i}(β − β_off) per node
        (the β_off subtraction happens in the caller so that the setpoint
        can vary per edge if needed).
      kp: traced proportional gain overriding ``cfg.kp`` — the simulation
        engines pass the gain here so it never keys a compile (and can be
        a per-draw value under vmap).

    Returns:
      (new_state, c_corr) where c_corr is the applied relative frequency
      correction per node.
    """
    if kp is None:
        kp = cfg.kp
    c_rel = kp * agg_err
    if cfg.kind == "proportional":
        return state, c_rel
    if cfg.kind == "pi":
        integ = state["integ"] + cfg.ki * agg_err
        return {**state, "integ": integ}, c_rel + integ
    # discrete: slew c_est toward c_rel in units of fs, bounded pulse budget.
    c_est = state["c_est"]
    want_pulses = jnp.round((c_rel - c_est) / cfg.fs)
    pulses = jnp.clip(want_pulses, -cfg.pulses_per_update, cfg.pulses_per_update)
    c_est = c_est + pulses * cfg.fs
    return {**state, "c_est": c_est}, c_est


def holdover_freeze(state_new, state_old, enabled):
    """Freeze controller state for nodes in clock holdover.

    The scenario subsystem (``repro.scenarios.NodeHoldover``) models a
    node losing its control loop: its oscillator keeps the last applied
    correction (ν frozen by the simulation engines) and its controller
    state — the PI integrator, the discrete actuator's ``c_est`` — must
    not keep evolving while the loop is open, or ``NodeReset`` would
    rejoin with garbage.  ``enabled`` is a boolean (N,) mask; disabled
    nodes keep ``state_old``.
    """
    return jax.tree_util.tree_map(
        lambda new, old: jnp.where(enabled, new, old), state_new, state_old)
