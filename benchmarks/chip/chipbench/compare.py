"""The comparison that decides ``correct``.

Each number is the widest gap, over every draw, node and record of the
compared calls, between what the program's timed calls returned and
what the reference computes from the same inputs:

    freq_ppm          ν records, ppm
    beta_frames       per-node net occupancy records, frames
    beta_peak_frames  the occupancy watermark max |β|, frames
    nu_extremes_ppm   the frequency watermarks (min and max ν), ppm

A splice's re-established λeff enters the occupancy of the link's two
nodes at every later record, so ``beta_frames`` holds the splice to the
reference: a λeff off by a frame moves those records by a frame.

A cell's limits file gives each number's limit; a number with no limit
is an error, never a pass.  A gap that is not finite, or an answer of
the wrong shape, reads as infinite.
"""
from __future__ import annotations

import math

import numpy as np


def _gap(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    g = float(np.abs(got - ref).max())
    return g if math.isfinite(g) else math.inf


def gaps(got: dict, ref: dict) -> dict:
    """The compared numbers of one call."""
    return {
        "freq_ppm": _gap(got["freq_ppm"], ref["freq_ppm"]),
        "beta_frames": _gap(got["beta"], ref["beta"]),
        "beta_peak_frames": _gap(got["beta_abs_max"], ref["beta_abs_max"]),
        "nu_extremes_ppm": max(_gap(got["nu_min_ppm"], ref["nu_min_ppm"]),
                               _gap(got["nu_max_ppm"], ref["nu_max_ppm"])),
    }


def judge(per_call: list, limits: dict):
    """(correct, failed calls, {number: {"value", "limit"}}) over the
    compared calls; each value is the widest over them."""
    if not per_call:
        return False, 0, {}
    names = sorted(set().union(*per_call))
    missing = [n for n in names if n not in limits]
    if missing:
        raise ValueError(f"no limit for {missing}")
    checks = {n: {"value": max(g[n] for g in per_call),
                  "limit": float(limits[n])} for n in names}
    failed = sum(any(not g[n] <= limits[n] for n in g) for g in per_call)
    return failed == 0, failed, checks
