"""The one traffic generator: a mix file's parameters to each call's inputs.

A call is one scenario run of ``draws`` oscillator draws over the
configuration's ``duration_s``.  Every call gets fresh draws, made from
``(seed, call index)`` alone, so that a call's inputs can be made again
after the window to check its answer; the fabric, its links and the
events are fixed for the whole run, as for a user sweeping draws.
"""
from __future__ import annotations

import numpy as np

WARMUP_CALL = -1   # the call index of the set-up's warm-up call


def draws(config: dict, traffic: dict, kinds, seed: int,
          call: int) -> np.ndarray:
    """(draws, nodes) float32 unadjusted oscillator offsets in ppm,
    uniform in ±``oscillator_ppm``."""
    from .reference import topology_nodes
    rng = np.random.default_rng([int(seed) % 2**64, int(call) + 1])
    lim = float(config["oscillator_ppm"])
    shape = (int(traffic["draws"]), topology_nodes(config["topology"], kinds))
    return rng.uniform(-lim, lim, shape).astype(np.float32)


def work_per_call(config: dict, traffic: dict, kinds) -> int:
    """Simulated node·periods of one call."""
    from .reference import periods_of, topology_nodes
    return (int(traffic["draws"]) * topology_nodes(config["topology"], kinds)
            * periods_of(config))
