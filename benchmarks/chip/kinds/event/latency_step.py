"""A cable swap (paper §5.6): at ``t_s`` both directed edges of ``link``
take the cable ``cable_m``; with ``reestablish`` their λeff is recomputed
from the live state so that their buffers restart at the β0 set-point.

``{"kind": "latency_step", "t_s": ..., "link": [a, b], "cable_m": ...,
"reestablish": true}``
"""
import numpy as np

from chipbench.reference import cable_frames


def period(ev: dict, config: dict) -> int:
    return int(round(ev["t_s"] / config["dt_s"]))


def apply(ev: dict, config: dict, live) -> None:
    edges = live.fabric.edges_of_link(*ev["link"])
    new_lat = cable_frames(config, ev["cable_m"])
    live.lat[edges] = new_lat
    if ev.get("reestablish", False):
        beta0 = config.get("beta0_frames", 0.0)
        s, d = live.fabric.src[edges], live.fabric.dst[edges]
        p64, n64 = live.psi.astype(np.float64), live.nu.astype(np.float64)
        live.lam[:, edges] = (beta0 - p64[:, s] + n64[:, s] * new_lat
                              + p64[:, d])


def program(ev: dict, topo, config: dict):
    from repro.scenarios import LatencyStep, edges_between
    return LatencyStep(t=ev["t_s"], edges=edges_between(topo, *ev["link"]),
                       cable_m=ev["cable_m"],
                       reestablish=bool(ev.get("reestablish", False)))
