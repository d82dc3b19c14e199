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

A cell whose mix has a ``guard`` adds two numbers, from the guard's
decisions (``Replay``), each exact:

    trip_records      held trip decisions (draw × record) in which the
                      program and the reference differ
    shift_frames      the widest gap, in frames, between a shift the
                      program applied and the reference's

A cell whose controller decides (FINC/FDEC pulses with integer readout;
``reference.decides``) is compared record by record from the program's
own state (``PulseReplay``), and adds two numbers:

    pulse_records     held node-records (draw × node × record) whose
                      pulse count differs from the reference's; exact
    replayed_share    the share of node-records the reference could not
                      tell and replayed; at most 1 − HELD_FLOOR, a limit
                      fixed here that no limits file changes

There ``freq_ppm`` and ``nu_extremes_ppm`` read the held node-records
(the replayed ones take the program's ν), and ``beta_frames`` and
``beta_peak_frames`` the reference's own records of each interval, which
start from the program's state at the record before.

A cell's limits file gives each number's limit; a number with no limit
is an error, never a pass.  A gap that is not finite, or an answer of
the wrong shape, reads as infinite.
"""
from __future__ import annotations

import math

import numpy as np

U32 = 2.0 ** -24   # float32 unit roundoff
# A pulse comparison that holds fewer node-records than this has checked
# too little to be correct.
HELD_FLOOR = 0.5
FIXED_LIMITS = {"replayed_share": 1.0 - HELD_FLOOR}


def _gap(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    g = float(np.abs(got - ref).max())
    return g if math.isfinite(g) else math.inf


class Replay:
    """The program's guard decisions, held to the reference where it can
    tell them and replayed where it cannot.

    The reference (``reference.simulate``) makes each decision from its
    own state and hands it here with its distance from turning:

    - a trip (draw × record) turns on the recorded net occupancy, the
      number ``beta_frames`` compares.  A run that passes that limit has
      every node's record within ``beta_frames``'s limit of the
      reference's, so where the reference's slack exceeds that limit the
      program's decision must be the reference's: it is held, and a
      difference counts in ``trip_records``;
    - a rotation rounds the potentials x = L⁺ d, where d is the same net
      occupancy as the record, summed in float64.  Each side's float32
      record lies within half the limit of its own d (float32 rounding,
      ~1e-4 frames at these magnitudes), and the records within the
      limit of each other, so the two d differ by less than twice the
      limit in every node, and a potential by less than ‖L⁺‖∞ (max row
      sum of |L⁺|) times that: the rounding bound.  Where the
      reference's potential lies farther than that from a half-integer
      it is held: the program's must be the same integer.

    Inside a bound the reference adopts the program's decision (a trip,
    or a potential within one frame of its own) and counts it as
    replayed; the program's potentials come from its shifts, summed
    along a spanning tree of the fabric, up to the constant that its held
    potentials fix.  ``shift_frames`` is the widest gap between the
    program's shifts and the potential differences the reference then
    applies: 0 where every held potential agrees and the program's
    shifts are a potential difference.  A program rotation at a record
    the reference never decides on counts in ``trip_records``.

    ``counts`` tallies the held and the replayed decisions;
    ``no_trips_held`` is how many held trip decisions were "no trip", so
    a call whose held decisions all trip shows that it never tested the
    band from inside.
    """

    def __init__(self, got: dict, limits: dict):
        self.bound_band = float(limits["beta_frames"])
        self.reframes = got.get("reframes", [])
        self.edges = got.get("edges")
        self.counts = {"trips_held": 0, "no_trips_held": 0,
                       "trips_replayed": 0, "potentials_held": 0,
                       "potentials_replayed": 0}
        self.trip_diff = 0
        self.shift_gap = 0.0

    def bind(self, fabric, pinv_norm: float) -> None:
        """Map the program's shifts onto the reference's edges and lay out
        the spanning tree; called once the reference has its fabric."""
        self.bound_pot = 2.0 * pinv_norm * self.bound_band
        self.src, self.dst = fabric.src, fabric.dst
        self.shift = {}
        order = (_edge_order(self.edges, fabric) if self.reframes
                 else None)
        for record, rows in self.reframes:
            rows = np.asarray(rows, np.int64)
            for b in np.flatnonzero(np.abs(rows).sum(axis=1) > 0):
                if order is None or rows.shape[1] != len(order):
                    # Shifts over other edges than the fabric's.
                    self.shift_gap = math.inf
                    row = np.zeros(len(self.src), np.int64)
                else:
                    row = rows[b][order]
                self.shift[(int(record) - 1, int(b))] = row
        self.pending = set(self.shift)
        self.levels = _tree_levels(fabric)

    def trips(self, record: int, own: np.ndarray, slack: np.ndarray):
        prog = np.array([(record, b) in self.shift for b in range(len(own))])
        self.pending -= {(record, b) for b in range(len(own))}
        held = slack > self.bound_band
        self.trip_diff += int(np.count_nonzero(held & (own != prog)))
        self.counts["trips_held"] += int(held.sum())
        self.counts["no_trips_held"] += int((held & ~own).sum())
        self.counts["trips_replayed"] += int((~held).sum())
        return np.where(held, own, prog)

    def potentials(self, record: int, draw: int, x: np.ndarray):
        r = np.rint(x)
        held = 0.5 - np.abs(x - r) > self.bound_pot
        self.counts["potentials_held"] += int(held.sum())
        self.counts["potentials_replayed"] += int((~held).sum())
        s = self.shift.get((record, draw))
        if s is None:
            return r
        q = np.zeros(len(r))
        for nodes, parents, edges in self.levels:
            q[nodes] = q[parents] - s[edges]
        off = (r - q)[held] if held.any() else np.rint(x - q)
        vals, cnt = np.unique(off, return_counts=True)
        cand = q + vals[np.argmax(cnt)]
        take = ~held & (np.abs(cand - r) <= 1)
        a = np.where(take, cand, r)
        gap = np.abs(s - (a[self.src] - a[self.dst])).max()
        self.shift_gap = max(self.shift_gap, float(gap))
        return a

    def numbers(self) -> dict:
        return {"trip_records": self.trip_diff + len(self.pending),
                "shift_frames": self.shift_gap}


class PulseReplay:
    """The program's FINC/FDEC pulse decisions under integer readout, held
    to the reference where it can tell them and replayed where it cannot.

    Why not the four numbers alone: a FINC/FDEC decision, and each edge's
    rounding to a whole frame, flips wherever two float32 evaluations of
    the same state differ, and the loop settles its edges on the rounding
    boundaries (it dithers), so two sound runs part within a few hundred
    periods.  The reference therefore starts every record interval from
    the program's own state at the record before
    (``reference._replay_pulses``).

    The rule (``kinds/controller/discrete.py``) is want = (kp·err −
    c_est)/fs, pulses = clip(rint(want), ±budget), c_est += pulses·fs.
    Inside the budget, c_est = fs·(m + ρ) with m an integer gives c_est' =
    fs·(ρ + rint(kp·err/fs − ρ)): the count after a period follows from
    that period's err alone.  So the count a record shows depends on the
    node's in-edge readouts and its own rounding in the interval's last
    period, and on nothing earlier; the reference takes those decisions
    again from the program's own state at that period, which the records
    at the interval's end give.  Two runs whose err differ by at most
    deg_i (one readout each edge) differ in their count by at most the
    spread (kp/fs)·deg_i + 1, and in what they want by at most twice it.

    A node-record (draw × node × record) is held when each of those
    decisions lies farther from turning than the bounds below, and every
    decision of the node in the interval stays inside the budget with
    room for what the program may want differently (``margin``: budget +
    1.5 − 2·spread − |want| > 0).  Held, the program's count n =
    rint(((ν − ν_u)/(1 + ν_u))/fs) (Fig. 16's reconstruction, which needs
    no program output beyond ν, so it works for any lane) must equal the
    reference's.  The float32 ν record resolves n: ν is about 1e-5 and
    its ulp about 9e-13, and its ppm record's ulp (about 1e-6 ppm) is the
    same 1e-12 in ν, against fs/2 ≥ 5e-9; the program's float32 c_est
    leaves the lattice of fs by far less than half a pulse (0.016 pulses
    over 10,000 periods at fs = 0.01 ppm).  Otherwise the node-record is
    replayed: the reference takes the program's ν there and counts it.

    The edge bound.  The program holds ψ in float32 (unit roundoff
    u = 2^-24) with |ψ| ≤ Ψ, and forms each β_e = ψ_s − ν_s·lat_e + λ_e −
    ψ_d with roundings of quantities no larger than Ψ' = Ψ + |ν|·lat + |λ|
    (three) and of |β_e| (one): within 3uΨ' + u|β_e| of exact.  Its net
    record sums deg_i of them, partial sums no larger than
    S_i = Σ_{e→i} |β_e|, so it lies within

        η_i = u·deg_i·(3Ψ' + S_i + 1.1·|ν|·lat)

    of the net of its state (the last term is ν's ppm record, relative
    error 1.1u, in ν·lat).  The reference's ψ from that record is off by
    L⁺ of that error, so edge e's β by at most G_e·η, with
    G_e = |L⁺_s· − L⁺_d·|.  ψ(R − 1) = ψ(R) − ν(R)·dt holds to
    u(Ψ + 2.1|ν|·dt) a node, the program's own β_e of that period to
    3uΨ' + u|β_e| again, and ν_s(R − 1) to the jitter of its last step,
    whose pulses differ from the reference's by at most twice the spread:
    2·spread·fs·(1 + |ν_u|), times lat_e.  The bound is their sum:

        tol_e = G_e·η + 2u(Ψ + 2.1|ν|·dt) + 3uΨ' + u|β_e| + lat_e·jitter_s

    Ψ is the reference's own largest |ψ| at the record, +1 % and one
    frame: it carries ψ's mean, which the program's follows to far less
    (a count one off moves one node by fs·dt, 6e-4 frames at 0.1 ppm).  A
    lane that keeps ψ smaller (centred) errs less.  An edge's readout is
    held where β_e lies farther than tol_e from a half-integer.

    The rounding bound.  The program forms want in float32: kp·err and
    its difference with c_est each to u of their size, and the quotient
    by fs to 4u (a TPU divides through a reciprocal).  Its c_est may have
    moved from the one its ν record shows by the R roundings of the
    interval's c_est + pulses·fs, each within u(c_max + budget·fs), with
    c_max = (|ν|max + |ν_u|)/(1 − |ν_u|), and by the record's own reading
    of ν (4.5u|ν|max: the ppm record and (ν − ν_u)/(1 + ν_u)).  So

        tol = (u(|kp·err| + |kp·err − c_est|) + c_gap)/fs + 4u|want|,
        c_gap = u(4.5·|ν|max + R·(c_max + budget·fs)),

    and a node's rounding is held where want lies farther than tol from a
    half-integer.

    ``counts`` tallies the held and the replayed node-records per call.
    """

    pulses = True

    def __init__(self, got: dict):
        self.freq = np.asarray(got["freq_ppm"], np.float64)
        self.beta = np.asarray(got["beta"], np.float64)
        self.counts = {"records_held": 0, "records_replayed": 0}
        self.count_diff = 0

    def bind(self, fabric, lap, nsum, shape, ctl) -> None:
        """The program's records against the reference's (B, T, N), the
        reference's float64 controller, whose rule is held, and how far an
        error in the net record moves each edge's β, the (E, N)
        |L⁺_s· − L⁺_d·|."""
        self.bad = self.freq.shape != shape or self.beta.shape != shape
        self.nodes = shape[2]
        self.src, self.nsum, self.ctl = fabric.src, nsum, ctl
        self.deg = nsum.deg.astype(np.float64)
        self.spread = ctl.kp / ctl.fs * self.deg + 1
        pinv = lap.pinv(np.eye(fabric.nodes))
        self.gain = np.abs(pinv[fabric.src] - pinv[fabric.dst])

    def record(self, t: int):
        """(ν, net occupancy) of the program's record t, (B, N) each."""
        if self.bad:
            nan = np.full((self.freq.shape[0], self.nodes), np.nan)
            return nan, nan
        return self.freq[:, t] / 1e6, self.beta[:, t]

    @staticmethod
    def state(nu, nu_u) -> dict:
        """The controller's state at a record, from its ν record."""
        return {"c_est": (nu - nu_u) / (1 + nu_u)}

    def count(self, nu, nu_u):
        """The accumulated pulse count a ν record shows (Fig. 16)."""
        return np.rint(self.state(nu, nu_u)["c_est"] / self.ctl.fs)

    def margin(self, net, state):
        """How far, in pulses, both runs' decisions of this period lie
        inside the budget (positive: neither is clipped)."""
        return (self.ctl.budget + 1.5 - 2 * self.spread
                - np.abs(self.ctl.want(net, state)))

    def edge_tolerance(self, psi_max, beta, nu_max, nu_u, lat, lam_max,
                       dt_frames):
        """tol_e of the last period's (B, E) β, from the (B,) bounds on
        |ψ| and |λ| and the (B, N) bound on |ν|."""
        jitter = 2 * self.spread * self.ctl.fs * (1 + np.abs(nu_u))
        nu = nu_max.max(axis=1, keepdims=True)
        big = (psi_max + nu[:, 0] * lat.max() + lam_max)[:, None]
        eta = U32 * self.deg * (3 * big + 1.1 * nu * lat.max()) + (
            U32 * self.deg * self.nsum(np.abs(beta)))
        return (eta @ self.gain.T
                + 2 * U32 * (psi_max[:, None] + 2.1 * nu * dt_frames)
                + 3 * U32 * big + U32 * np.abs(beta)
                + lat * jitter[:, self.src])

    def slack(self, net, nu_u, state, periods: int, nu_max):
        """Distance, in pulses, of each node's rounding from turning, less
        the rounding bound; ``state`` is the reference's at the last
        period, ``nu_max`` a bound on |ν| over the interval's
        ``periods``."""
        ctl = self.ctl
        want = ctl.want(net, state)
        rel = want * ctl.fs + state["c_est"]
        c_max = (nu_max + np.abs(nu_u)) / (1 - np.abs(nu_u))
        c_gap = U32 * (4.5 * nu_max
                       + periods * (c_max + ctl.budget * ctl.fs))
        tol = (U32 * (np.abs(rel) + np.abs(rel - state["c_est"])) + c_gap
               ) / ctl.fs + 4 * U32 * np.abs(want)
        return 0.5 - np.abs(want - np.rint(want)) - tol

    def decide(self, t: int, own, got, held, nu_u):
        """The ν the reference records at t: its own where the node-record
        is held, the program's where it is replayed."""
        held = held & np.isfinite(got)
        self.count_diff += int(np.count_nonzero(
            held & (self.count(own, nu_u) != self.count(got, nu_u))))
        self.counts["records_held"] += int(held.sum())
        self.counts["records_replayed"] += int((~held).sum())
        return np.where(held, own, got)

    def numbers(self) -> dict:
        c = self.counts
        total = c["records_held"] + c["records_replayed"]
        return {"pulse_records": self.count_diff,
                "replayed_share": (c["records_replayed"] / total
                                   if total else math.inf)}


def _edge_order(edges, fabric):
    """For each reference edge, the index of the program's edge with the
    same (src, dst); None where the program's edges are not the same
    set."""
    if edges is None:
        return None
    edges = np.asarray(edges, np.int64)
    at = {(int(a), int(b)): i for i, (a, b) in enumerate(edges)}
    try:
        order = np.array([at[(int(a), int(b))]
                          for a, b in zip(fabric.src, fabric.dst)])
    except KeyError:
        return None
    return order if len(at) == len(order) else None


def _tree_levels(fabric):
    """A breadth-first spanning tree from node 0, level by level:
    [(nodes, their parents, the edges parent → node)]."""
    out_edges = {}
    for e, (a, b) in enumerate(zip(fabric.src.tolist(),
                                   fabric.dst.tolist())):
        out_edges.setdefault(a, []).append((b, e))
    seen = np.zeros(fabric.nodes, bool)
    seen[0] = True
    frontier, levels = [0], []
    while frontier:
        nodes, parents, edges = [], [], []
        for a in frontier:
            for b, e in out_edges.get(a, ()):
                if not seen[b]:
                    seen[b] = True
                    nodes.append(b)
                    parents.append(a)
                    edges.append(e)
        if nodes:
            levels.append((np.array(nodes), np.array(parents),
                           np.array(edges)))
        frontier = nodes
    if not seen.all():
        raise ValueError("the fabric is not connected")
    return levels


def check_call(config: dict, traffic: dict, kinds, ppm, got: dict,
               limits: dict):
    """(the compared numbers, the replay's decision counts or None) of one
    call whose inputs were ``ppm`` and whose answer is ``got``."""
    from . import reference
    fabric = reference.build_fabric(config, kinds)
    pulses = reference.decides(
        reference.controller(config, kinds, fabric, np.float64))
    if pulses and traffic.get("guard"):
        raise ValueError("no replay holds a guard and pulses together yet")
    replay = (PulseReplay(got) if pulses
              else Replay(got, limits) if traffic.get("guard") else None)
    ref = reference.simulate(config, traffic, kinds, ppm, replay=replay)
    return (gaps(got, ref, replay),
            dict(replay.counts) if replay is not None else None)


def gaps(got: dict, ref: dict, replay=None) -> dict:
    """The compared numbers of one call (with a ``replay``, its own two as
    well)."""
    out = {
        "freq_ppm": _gap(got["freq_ppm"], ref["freq_ppm"]),
        "beta_frames": _gap(got["beta"], ref["beta"]),
        "beta_peak_frames": _gap(got["beta_abs_max"], ref["beta_abs_max"]),
        "nu_extremes_ppm": max(_gap(got["nu_min_ppm"], ref["nu_min_ppm"]),
                               _gap(got["nu_max_ppm"], ref["nu_max_ppm"])),
    }
    if replay is not None:
        out.update(replay.numbers())
    return out


def judge(per_call: list, limits: dict):
    """(correct, failed calls, {number: {"value", "limit"}}) over the
    compared calls; each value is the widest over them.  ``FIXED_LIMITS``
    take the place of a limits file's."""
    if not per_call:
        return False, 0, {}
    limits = {**limits, **FIXED_LIMITS}
    names = sorted(set().union(*per_call))
    missing = [n for n in names if n not in limits]
    if missing:
        raise ValueError(f"no limit for {missing}")
    checks = {n: {"value": max(g[n] for g in per_call),
                  "limit": float(limits[n])} for n in names}
    failed = sum(any(not g[n] <= limits[n] for n in g) for g in per_call)
    return failed == 0, failed, checks
