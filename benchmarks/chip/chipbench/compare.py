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


def gaps(got: dict, ref: dict, replay: Replay = None) -> dict:
    """The compared numbers of one call (with a ``replay``, the guard's
    two as well)."""
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
