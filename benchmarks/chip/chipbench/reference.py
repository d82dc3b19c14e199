"""Plain reference of the bittide frame model, in NumPy.

It imports nothing of the system under test and takes nothing it made:
the fabric, the link latencies, the controller and the events are
rebuilt here from the configuration and traffic files, through the
reference halves of their kind files (``spec.Kinds``), and the inputs
are the oscillator draws the harness generated.  The model
(arXiv:2503.05033 §6, in relative coordinates ψ = θ − ω·t,
ν = ω/ω_nom − 1) advances every draw one control period at a time:

    β_e   = ψ_src − ν_src·ω·l_e + λeff_e − ψ_dst            per directed edge
    net_i = Σ_{e→i} β_e
    ν_i'  = the controller kind's update of net_i           (proportional:
            ν_u,i + c_i + ν_u,i·c_i, c_i = kp·(net_i − β_off·deg_i))
    ψ_i'  = ψ_i + ν_i'·ω·dt

and every ``record_every`` periods records ν (ppm) and net_i of the
post-update state.  An event kind mutates the live state (``Live``) at
the start of its period: a latency step swaps the cable of both directed
edges of a link and, with ``reestablish``, recomputes their λeff from the
live state so that their buffers restart at the β0 set-point.

A mix with a ``guard`` entry (``{"margin_frames": m}``) closes the
reframing loop the kernel lanes run (the in-kernel guard band, the
runner's splice), with D the configuration's ``elastic_buffer_depth``:

- trip rule: after record t, a draw trips when any node's recorded net
  occupancy leaves the degree-scaled band, net_i > (target + g)·deg_i or
  net_i < (target − g)·deg_i, with g = D/2 − m and target 0 (strict
  inequalities).  The trip is seen at the record that crosses, so the
  exposure is one record; the rotation is live from record t + 1, and no
  draw rotates after the run's last record;
- rotation (graph mode, arXiv:2504.07044): from the draw's live state at
  record t, in float64, d_i = Σ_{e→i} β_e − target·deg_i; the node
  potentials x = L⁺ d solve the in-degree Laplacian L = D_in − A_in
  (conjugate gradients, ``Laplacian``; the mean of x is zero); each is
  rounded to an integer, and every edge's λeff moves by the integer
  shift x_src − x_dst, which conserves every round-trip latency.

A ``replay`` (``compare.Replay``) sees each decision beside the
reference's distance from changing it, and may hand back the program's
decision where that distance is inside the rounding bound; without one
the reference keeps its own.

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
from typing import Optional

import numpy as np

PRECISIONS = ("float32", "high")


@dataclasses.dataclass(frozen=True)
class Fabric:
    """A directed graph with the physical latency of every edge."""

    nodes: int
    src: np.ndarray        # (E,) int64
    dst: np.ndarray        # (E,) int64
    lat_frames: np.ndarray  # (E,) float64, one-way latency in frames
    transitive: bool = False  # every node maps to every other

    def edges_of_link(self, a: int, b: int) -> np.ndarray:
        hit = ((self.src == a) & (self.dst == b)) | (
            (self.src == b) & (self.dst == a))
        idx = np.nonzero(hit)[0]
        if len(idx) != 2:
            raise ValueError(f"no bidirectional link between {a} and {b}")
        return idx


@dataclasses.dataclass
class Live:
    """The state an event may change at the start of its period."""

    fabric: Fabric
    psi: np.ndarray    # (B, N) float32
    nu: np.ndarray     # (B, N) float32
    lat: np.ndarray    # (E,) float32 physical latency, frames
    lam: np.ndarray    # (B, E) float64 λeff


def topology_nodes(topology: dict, kinds) -> int:
    return kinds.topology.nodes(topology)


def cable_frames(config: dict, cable_m: float) -> float:
    """One-way latency in frames of a cable: flight time plus pipeline."""
    return (cable_m / config["signal_velocity_m_per_s"]
            * config["omega_nom_hz"] + config["pipe_frames"])


def build_fabric(config: dict, kinds) -> Fabric:
    t = config["topology"]
    pairs = np.asarray(kinds.topology.pairs(t), np.int64)
    lat = np.full(len(pairs), cable_frames(config, config["cable_m"]))
    return Fabric(nodes=kinds.topology.nodes(t), src=pairs[:, 0],
                  dst=pairs[:, 1], lat_frames=lat,
                  transitive=bool(getattr(kinds.topology, "TRANSITIVE",
                                          False)))


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


class Laplacian:
    """The in-degree Laplacian L = D_in − A_in of a bidirectional fabric,
    applied edge by edge, and L⁺ by conjugate gradients in float64."""

    RTOL = 1e-13       # residual over right-hand side, per row
    MAX_ITER = 20000

    def __init__(self, fabric: Fabric, nsum: _NodeSum):
        fwd = set(zip(fabric.src.tolist(), fabric.dst.tolist()))
        if fwd != set(zip(fabric.dst.tolist(), fabric.src.tolist())):
            raise ValueError("the guard needs a bidirectional fabric "
                             "(a symmetric Laplacian)")
        self.src = fabric.src
        self.nsum = nsum
        self.deg = nsum.deg.astype(np.float64)
        self.nodes = fabric.nodes

    def apply(self, x: np.ndarray) -> np.ndarray:
        return self.deg * x - self.nsum(x[:, self.src])

    def pinv(self, d: np.ndarray) -> np.ndarray:
        """L⁺ d for each row of (R, N) ``d``: the solution of L x = d −
        mean(d) with mean zero (the constants are L's null space)."""
        b = np.asarray(d, np.float64)
        b = b - b.mean(axis=1, keepdims=True)
        x = np.zeros_like(b)
        r = b.copy()
        p = r.copy()
        rs = (r * r).sum(axis=1)
        tol = (self.RTOL ** 2) * np.maximum(rs, 1e-300)
        for _ in range(self.MAX_ITER):
            live = rs > tol
            if not live.any():
                break
            ap = self.apply(p)
            pap = (p * ap).sum(axis=1)
            alpha = np.where(live, rs / np.where(live, pap, 1.0), 0.0)
            x += alpha[:, None] * p
            r -= alpha[:, None] * ap
            rs_new = (r * r).sum(axis=1)
            beta = np.where(live, rs_new / np.where(live, rs, 1.0), 0.0)
            p = r + beta[:, None] * p
            rs = np.where(live, rs_new, rs)
        else:
            raise RuntimeError("conjugate gradients did not converge")
        return x - x.mean(axis=1, keepdims=True)

    def pinv_row_norm(self, transitive: bool) -> float:
        """max_i Σ_j |L⁺_ij|: how far a change of d by at most 1 in every
        node can move a potential.  On a transitive fabric every row has
        the same norm, so node 0's is solved alone."""
        rows = [0] if transitive else list(range(self.nodes))
        out = 0.0
        for lo in range(0, len(rows), 256):
            idx = rows[lo:lo + 256]
            e = np.zeros((len(idx), self.nodes))
            e[np.arange(len(idx)), idx] = 1.0
            out = max(out, float(np.abs(self.pinv(e)).sum(axis=1).max()))
        return out


class Guard:
    """The reframing guard of a mix's ``guard`` entry (module docstring)."""

    TARGET = 0.0

    def __init__(self, depth: int, guard: dict, fabric: Fabric,
                 nsum: _NodeSum):
        self.half = depth / 2.0 - float(guard["margin_frames"])
        if self.half <= 0:
            raise ValueError("guard band depth/2 − margin must be positive")
        self.fabric = fabric
        self.lap = Laplacian(fabric, nsum)
        self.deg = nsum.deg.astype(np.float64)
        self.pinv_norm = self.lap.pinv_row_norm(fabric.transitive)

    def trips(self, net: np.ndarray):
        """(tripped, slack) of (B, N) recorded net occupancies: slack is
        how far the draw's decision is from turning, in frames (how far
        its farthest node lies outside the band, or its nearest inside)."""
        net = np.asarray(net, np.float64)
        hi = (self.TARGET + self.half) * self.deg
        lo = (self.TARGET - self.half) * self.deg
        over = np.maximum(net - hi, lo - net).max(axis=1)
        tripped = over > 0
        return tripped, np.abs(over)

    def potentials(self, psi, nu, lat, lam) -> np.ndarray:
        """(R, N) float64 potentials x = L⁺ d of R draws' live state."""
        f = self.fabric
        p64, n64 = psi.astype(np.float64), nu.astype(np.float64)
        beta = (p64[:, f.src] - n64[:, f.src] * lat.astype(np.float64)
                + lam - p64[:, f.dst])
        d = self.lap.nsum(beta) - self.TARGET * self.deg
        return self.lap.pinv(d)


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def _contract_high(x_src: np.ndarray) -> np.ndarray:
    """One adjacency term w·x as a bf16_3x product, w = 1: x_hi + x_lo
    (the a_lo·b_hi term vanishes, since 1 is exact in bfloat16)."""
    hi = _bf16(x_src)
    return hi + _bf16(x_src - hi)


def _events(config: dict, traffic: dict, kinds) -> dict:
    """{period: [(event kind module, event)]} in the mix's order."""
    out = {}
    for ev in traffic.get("events", []):
        mod = kinds.event(ev)
        out.setdefault(mod.period(ev, config), []).append((mod, ev))
    return out


def simulate(config: dict, traffic: dict, kinds, ppm: np.ndarray,
             precision: str = "float32", replay=None) -> dict:
    """Run one call's draws; returns the records the program reports.

    Args:
      kinds: the cell's ``spec.Kinds``.
      ppm: (B, N) unadjusted oscillator offsets, ppm.
      replay: where the mix has a guard, an object with ``bind(fabric,
        pinv_norm)``, ``trips(record, own, slack)`` and
        ``potentials(record, draw, x)`` (``compare.Replay``) that decides
        what the reference applies; None keeps the reference's own.
    Returns a dict of float64 arrays of float32 values: ``freq_ppm`` and
    ``beta`` (B, T, N), and the watermarks ``beta_abs_max``,
    ``nu_min_ppm``, ``nu_max_ppm`` (B, N); with a guard also
    ``reframes``, [(record, (B, E) int64 shift)], and ``edges``, the
    (E, 2) (src, dst) pairs the shifts index.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    fab = build_fabric(config, kinds)
    dt = np.float32
    dt_frames = dt(config["omega_nom_hz"] * config["dt_s"])
    periods = periods_of(config)
    rec_every = int(traffic["record_every"])
    if periods % rec_every:
        raise ValueError("periods must be a multiple of record_every")
    records = periods // rec_every
    events = _events(config, traffic, kinds)

    nsum = _NodeSum(fab.dst, fab.nodes)
    deg = nsum.deg.astype(dt)
    control = kinds.controller.reference(config["controller"], deg, dt)
    guard: Optional[Guard] = None
    if traffic.get("guard"):
        guard = Guard(config["elastic_buffer_depth"], traffic["guard"],
                      fab, nsum)
        if replay is not None:
            replay.bind(fab, guard.pinv_norm)
    b = ppm.shape[0]
    live = Live(fabric=fab, psi=np.zeros((b, fab.nodes), dt), nu=None,
                lat=fab.lat_frames.astype(dt),
                lam=np.full((b, len(fab.src)),
                            config.get("beta0_frames", 0.0), np.float64))
    nu_u = (np.asarray(ppm, np.float64) * 1e-6).astype(dt)
    live.nu = nu_u.copy()

    if precision != "high":
        def net(psi, nu):
            """Σ_{e→i} β_e, edge by edge."""
            return nsum(psi[:, fab.src] - nu[:, fab.src] * live.lat
                        + live.lam.astype(dt) - psi[:, fab.dst])
    else:
        def net(psi, nu):
            """The program's form: per-class contraction of ψ − ν·lat_c
            at Precision.HIGH, minus ψ·deg, plus the λeff fold."""
            lat = live.lat
            classes = np.unique(lat)
            acc = np.zeros_like(psi)
            for c in classes:
                x = psi - nu * dt(c)
                term = _contract_high(x[:, fab.src])
                term[:, lat != c] = 0
                acc = acc + nsum(term)
            return acc - psi * deg + lam_t

    freq, beta, reframes = [], [], []
    lam_t = nsum(live.lam).astype(dt)
    for p in range(periods):
        for mod, ev in events.get(p, ()):
            mod.apply(ev, config, live)
            lam_t = nsum(live.lam).astype(dt)
        live.nu = control(net(live.psi, live.nu), nu_u, live.nu)
        live.psi = live.psi + live.nu * dt_frames
        if (p + 1) % rec_every:
            continue
        t = (p + 1) // rec_every - 1
        psi, nu = live.psi, live.nu
        freq.append(nu.astype(np.float64) * 1e6)
        # The record is β of the post-update state; β is invariant
        # under a uniform ψ shift, so centre ψ first.
        psi_c = psi - psi.mean(axis=1, keepdims=True)
        rec = net(psi_c, nu)
        beta.append(rec.astype(np.float64))
        if guard is None or t >= records - 1:
            continue
        tripped, slack = guard.trips(rec)
        if replay is not None:
            tripped = replay.trips(t, tripped, slack)
        rows = np.flatnonzero(tripped)
        if not rows.size:
            continue
        x = guard.potentials(psi[rows], nu[rows], live.lat, live.lam[rows])
        shift = np.zeros(live.lam.shape, np.int64)
        for j, bi in enumerate(rows):
            pot = (np.rint(x[j]) if replay is None
                   else replay.potentials(t, int(bi), x[j]))
            pot = np.asarray(pot, np.int64)
            shift[bi] = pot[fab.src] - pot[fab.dst]
        live.lam = live.lam + shift
        lam_t = nsum(live.lam).astype(dt)
        reframes.append((t + 1, shift))
    freq = np.stack(freq, axis=1)
    beta = np.stack(beta, axis=1)
    out = {"freq_ppm": freq, "beta": beta,
           "beta_abs_max": np.abs(beta).max(axis=1),
           "nu_min_ppm": freq.min(axis=1), "nu_max_ppm": freq.max(axis=1)}
    if guard is not None:
        out["reframes"] = reframes
        out["edges"] = np.stack([fab.src, fab.dst], axis=1)
    return out
