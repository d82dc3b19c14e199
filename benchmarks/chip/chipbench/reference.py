"""Plain reference of the bittide frame model, in NumPy.

It imports nothing of the system under test and takes nothing it made:
the fabric, the link latencies and the events are rebuilt here from the
configuration and traffic files, and the inputs are the oscillator draws
the harness generated.  The model (arXiv:2503.05033 §6, in relative
coordinates ψ = θ − ω·t, ν = ω/ω_nom − 1) advances every draw one control
period at a time:

    β_e   = ψ_src − ν_src·ω·l_e + λeff_e − ψ_dst            per directed edge
    err_i = Σ_{e→i} (β_e − β_off)
    c_i   = kp·err_i
    ν_i'  = ν_u,i + c_i + ν_u,i·c_i                          ((1+ν_u)(1+c) − 1)
    ψ_i'  = ψ_i + ν_i'·ω·dt

and every ``record_every`` periods records ν (ppm) and the per-node net
occupancy Σ_{e→i} β_e of the post-update state.  A latency step swaps the
cable of both directed edges of a link at a period boundary; with
``reestablish`` it recomputes those edges' λeff from the live state so
that their buffers restart at the β0 set-point.

``precision="float32"`` is the reference: the model in the precision the
configuration states, float32, with every neighbour sum taken edge by
edge in float32 — what a float32 matmul at ``Precision.HIGHEST`` gives.
``precision="high"`` is the control: the same model in float32, with the
neighbour sum taken in the program's algebraic form,
Σ_c A_c·(ψ − ν·lat_c) − ψ·deg + lamsum, and that contraction computed
as TPU ``Precision.HIGH`` computes a float32 matmul — three bfloat16
passes (a_hi·b_hi + a_hi·b_lo + a_lo·b_hi, summed in float32).  It is
the step below the ``Precision.HIGHEST`` contraction the configuration
states.
"""
from __future__ import annotations

import dataclasses

import numpy as np

PRECISIONS = ("float32", "high")


@dataclasses.dataclass(frozen=True)
class Fabric:
    """A directed graph with the physical latency of every edge."""

    nodes: int
    src: np.ndarray        # (E,) int64
    dst: np.ndarray        # (E,) int64
    lat_frames: np.ndarray  # (E,) float64, one-way latency in frames

    def edges_of_link(self, a: int, b: int) -> np.ndarray:
        hit = ((self.src == a) & (self.dst == b)) | (
            (self.src == b) & (self.dst == a))
        idx = np.nonzero(hit)[0]
        if len(idx) != 2:
            raise ValueError(f"no bidirectional link between {a} and {b}")
        return idx


def _pairs(topology: dict) -> list:
    kind = topology["kind"]
    if kind == "fully_connected":
        n = int(topology["nodes"])
        return [(i, j) for i in range(n) for j in range(n) if i != j]
    if kind == "torus3d":
        k = int(topology["k"])
        out = []
        for x in range(k):
            for y in range(k):
                for z in range(k):
                    me = (x * k + y) * k + z
                    for dx, dy, dz in ((1, 0, 0), (0, 1, 0), (0, 0, 1)):
                        nb = (((x + dx) % k) * k + (y + dy) % k) * k + (
                            z + dz) % k
                        out += [(me, nb), (nb, me)]
        return out
    raise ValueError(f"unknown topology kind {kind!r}")


def topology_nodes(topology: dict) -> int:
    if topology["kind"] == "fully_connected":
        return int(topology["nodes"])
    if topology["kind"] == "torus3d":
        return int(topology["k"]) ** 3
    raise ValueError(f"unknown topology kind {topology['kind']!r}")


def cable_frames(config: dict, cable_m: float) -> float:
    """One-way latency in frames of a cable: flight time plus pipeline."""
    return (cable_m / config["signal_velocity_m_per_s"]
            * config["omega_nom_hz"] + config["pipe_frames"])


def build_fabric(config: dict) -> Fabric:
    pairs = np.asarray(_pairs(config["topology"]), np.int64)
    lat = np.full(len(pairs), cable_frames(config, config["cable_m"]))
    return Fabric(nodes=topology_nodes(config["topology"]), src=pairs[:, 0],
                  dst=pairs[:, 1], lat_frames=lat)


def periods_of(config: dict) -> int:
    return int(round(config["duration_s"] / config["dt_s"]))


class _NodeSum:
    """Σ over the in-edges of every node, for (B, E) edge values."""

    def __init__(self, dst: np.ndarray, nodes: int):
        self.order = np.argsort(dst, kind="stable")
        counts = np.bincount(dst, minlength=nodes)
        if not counts.all():
            raise ValueError("every node needs an in-edge")
        self.starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        self.deg = counts

    def __call__(self, x_e: np.ndarray) -> np.ndarray:
        return np.add.reduceat(x_e[:, self.order], self.starts, axis=1)


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def _contract_high(x_src: np.ndarray) -> np.ndarray:
    """One adjacency term w·x as a bf16_3x product, w = 1: x_hi + x_lo
    (the a_lo·b_hi term vanishes, since 1 is exact in bfloat16)."""
    hi = _bf16(x_src)
    return hi + _bf16(x_src - hi)


def _latency_events(config: dict, traffic: dict, fabric: Fabric):
    """{period: [(edge ids, new latency frames, reestablish)]}."""
    out = {}
    for ev in traffic.get("events", []):
        if ev["kind"] != "latency_step":
            raise ValueError(f"unknown event kind {ev['kind']!r}")
        p = int(round(ev["t_s"] / config["dt_s"]))
        edges = fabric.edges_of_link(*ev["link"])
        out.setdefault(p, []).append(
            (edges, cable_frames(config, ev["cable_m"]),
             bool(ev.get("reestablish", False))))
    return out


def simulate(config: dict, traffic: dict, ppm: np.ndarray,
             precision: str = "float32") -> dict:
    """Run one call's draws; returns the records the program reports.

    Args:
      ppm: (B, N) unadjusted oscillator offsets, ppm.
    Returns a dict of float64 arrays of float32 values: ``freq_ppm`` and
    ``beta`` (B, T, N), and the watermarks ``beta_abs_max``,
    ``nu_min_ppm``, ``nu_max_ppm`` (B, N).
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    fab = build_fabric(config)
    dt = np.float32
    ctrl = config["controller"]
    if ctrl["kind"] != "proportional":
        raise ValueError("the reference implements the proportional "
                         "controller")
    kp = dt(ctrl["kp"])
    boff = dt(ctrl.get("beta_off_frames", 0.0))
    dt_frames = dt(config["omega_nom_hz"] * config["dt_s"])
    periods = periods_of(config)
    rec_every = int(traffic["record_every"])
    if periods % rec_every:
        raise ValueError("periods must be a multiple of record_every")
    events = _latency_events(config, traffic, fab)

    nsum = _NodeSum(fab.dst, fab.nodes)
    deg = nsum.deg.astype(dt)
    lat = fab.lat_frames.astype(dt)
    b = ppm.shape[0]
    lam = np.full((b, len(lat)), config.get("beta0_frames", 0.0),
                  np.float64)
    nu_u = (np.asarray(ppm, np.float64) * 1e-6).astype(dt)
    psi = np.zeros((b, fab.nodes), dt)
    nu = nu_u.copy()

    if precision != "high":
        def net(psi, nu):
            """Σ_{e→i} β_e, edge by edge."""
            return nsum(psi[:, fab.src] - nu[:, fab.src] * lat
                        + lam.astype(dt) - psi[:, fab.dst])
    else:
        def net(psi, nu):
            """The program's form: per-class contraction of ψ − ν·lat_c
            at Precision.HIGH, minus ψ·deg, plus the λeff fold."""
            classes = np.unique(lat)
            acc = np.zeros_like(psi)
            for c in classes:
                x = psi - nu * dt(c)
                term = _contract_high(x[:, fab.src])
                term[:, lat != c] = 0
                acc = acc + nsum(term)
            return acc - psi * deg + lam_t

    freq, beta = [], []
    lam_t = nsum(lam).astype(dt)
    for p in range(periods):
        for edges, new_lat, reest in events.get(p, ()):
            lat[edges] = new_lat
            if reest:
                beta0 = config.get("beta0_frames", 0.0)
                s, d = fab.src[edges], fab.dst[edges]
                p64, n64 = psi.astype(np.float64), nu.astype(np.float64)
                lam[:, edges] = (beta0 - p64[:, s] + n64[:, s] * new_lat
                                 + p64[:, d])
            lam_t = nsum(lam).astype(dt)
        err = net(psi, nu) - boff * deg
        c = kp * err
        nu = nu_u + c + nu_u * c
        psi = psi + nu * dt_frames
        if (p + 1) % rec_every == 0:
            freq.append(nu.astype(np.float64) * 1e6)
            # The record is β of the post-update state; β is invariant
            # under a uniform ψ shift, so centre ψ first.
            psi_c = psi - psi.mean(axis=1, keepdims=True)
            beta.append(net(psi_c, nu).astype(np.float64))
    freq = np.stack(freq, axis=1)
    beta = np.stack(beta, axis=1)
    return {"freq_ppm": freq, "beta": beta,
            "beta_abs_max": np.abs(beta).max(axis=1),
            "nu_min_ppm": freq.min(axis=1), "nu_max_ppm": freq.max(axis=1)}
