"""Execute a compiled scenario by chaining the simulation engines.

The runner walks the compiled segments in order and, inside each segment,
replays fixed-size chunks of ``chunk_records`` telemetry records through
ONE simulation engine, threading the full simulator state — ψ, ν, the
controller state, and the per-edge λeff constants — across every
boundary.  Because every traced quantity (link latencies, λeff folds,
edge weights, controller masks, gains, ν_u) changed *data* rather than
*shape*, the whole scenario compiles each engine exactly once; the
no-recompile guard in ``tests/test_scenarios.py`` pins this.

Engines:

``segment-sum``   the production edge-list simulator
                  (:func:`repro.core.frame_model.simulate` /
                  ``simulate_ensemble``) — records per-edge (T, E) β
                  telemetry, supports every controller kind, quantization,
                  telemetry noise, and fully heterogeneous per-draw (B, E)
                  links.
``fused``/``tiled``/``per-step``/``auto``
                  the dense Pallas lanes, driven directly at the jitted
                  engine layer — ν telemetry plus, with
                  ``record_beta=True``, in-kernel per-node net occupancy
                  (T, N) β telemetry (frames; see
                  ``repro.kernels.bittide_step``); proportional
                  controller with continuous readout on every dense
                  lane, and on the fused lane also the FINC/FDEC
                  controller and integer buffer readout
                  (``quantize_beta``), whose ``c_est`` the runner threads
                  like ψ and ν; shared base links (per-draw λeff from
                  re-establishment is supported; per-draw base latencies
                  belong on segment-sum).  The per-segment (C, N, N)
                  adjacency stacks are built ONCE up front, on the
                  device (:func:`_build_dense_stacks`): repeated
                  parameter sets (swap-back events) are deduped, and
                  each unique stack uploads only its O(E) edge list,
                  which one jitted scatter turns into a fresh device
                  buffer — no N² adjacency exists on the host, and the
                  chunk loop then replays the jitted engine with zero
                  host rebuilds and zero re-transfers.
``sparse``        the edge-major ELL Pallas lane
                  (``repro.kernels.bittide_sparse``) — same telemetry
                  contract as the dense lanes, proportional controller
                  and continuous readout, but O(N·deg) per period:
                  bounded-degree scenario studies scale past the dense
                  lanes (up to ~5·10⁴ nodes at B = 8 on one TPU).  No
                  latency classes exist here (every slot carries its edge's own
                  latency in frames), so fully heterogeneous per-draw
                  (B, E) links AND per-draw (B, E) edge weights — chaos
                  campaigns with per-draw LinkDrop victims — run
                  compiled, the regimes the dense lanes must reject.
                  Per-segment slot tables are deduped by byte content
                  (:func:`_build_sparse_tables`), the sparse analogue of
                  the dense stack builder.

Segment plans (``repro.scenarios.plan``): the kernel lanes keep one
fabric's dense stacks or sparse slot tables across calls, and with them
each segment's engine choice and padded device inputs for the last
controller, draw count, lane and telemetry variant.  A call whose inputs
equal the last call's by value (new draws only) does only what the draws
change: ν_u, the re-establish splice, a per-draw λeff fold (one bincount
over the plan's flat index) and the first state.

Every kernel lane — fused, tiled, per-step, sparse — runs through ONE
chunk loop.  Each segment is prepped into a lane value (``_Lane``: its
label and tile, its ``engine_dispatch`` fields, and its chunk launch);
the loop owns the rest once: the stop cap, the ``chunk`` spans, the
read-back, the watermark merge, the guard evaluation and the rotation
with its re-prep.  The batched lanes launch every draw at once and read
the chunk back in one transfer; the per-step lane launches each draw on
its own and resyncs them on the host after a guard trip.

β splicing: occupancy is a pure function of the threaded (ψ, ν, λeff)
state in relative coordinates, so dense β telemetry splices across
segment boundaries exactly like ψ/ν — bit-identically for a no-event
split, and through a LatencyStep re-establishment the first post-event
record reflects the re-filled buffer (the new λeff fold) just as the
segment-sum recording does.

λeff semantics (see ``repro.scenarios.events``): a plain LatencyStep
keeps λeff constant — occupancy is continuous through the swap and the
logical latency λ = λeff + ω·l shifts by exactly the in-flight frame
count, the paper's Table-2 observation.  ``reestablish`` recomputes λeff
from the live state so the buffer restarts at its β0 setpoint.

Closed-loop buffer re-centering (``auto_reframe=``): real elastic
buffers are 32 frames deep, and the hardware keeps them there by
*reframing* — rotating read pointers so occupancy returns to the
setpoint, trading λ for headroom (paper §4.2; arXiv:2504.07044).  With
``auto_reframe`` enabled the runner closes that loop in simulation,
with the guard check placed per lane.  On the kernel lanes the guard
runs IN-KERNEL: every measure pass compares the per-node net occupancy
against the per-draw degree-scaled band ``target ± (depth/2 − margin)``
and freezes the chunk at the first tripping record (post-trip records
are predicated no-ops), so the splice lands one record period after the
crossing regardless of ``chunk_records``, and the resumed partial chunk
re-enters the same executable through a traced stop cap.  On
segment-sum the runner inspects each completed chunk's per-edge record:
the record is per NODE but the buffer wall is per EDGE, so the trigger
reconstructs the graph-consistent per-edge occupancy estimate — node
potentials from the Laplacian pseudo-inverse of the net record,
differenced along each edge — before comparing against the guard
(exposure up to one chunk there).  Margins default to the per-draw
:func:`repro.core.envelopes.reframe_guard_margins`.  When tripped, the
runner splices a pointer rotation computed from the live threaded state
(:func:`repro.core.reframing.graph_shifts`): integer
node potentials solve the Laplacian least-squares problem against the
net occupancy deviation, every edge's λeff shifts by
``x_src − x_dst``, and ALL cycle sums of λ — every RTT — are conserved
by construction.  The shifts rewrite only traced inputs (the per-node
``lamsum`` fold on the fused/tiled lanes, the λeff tensor on the
per-step lane, ``links.beta0`` on segment-sum), so the SAME compiled
engine continues across every splice: long scenarios whose
DriftRamp/FreqStep excursions would overflow a 32-deep buffer now run
indefinitely inside it, at the cost of a per-splice λ rotation recorded
in ``ScenarioResult.reframes``.

Per-draw chaos batches (``repro.scenarios.chaos``): when the compiled
scenario carries per-draw event parameters (B distinct FreqStep sizes,
DriftRamp slopes, LatencyStep Δl, holdover victims …), every lowered
quantity is threaded as a traced (B, ·) array — (B, N) ν_u/dppm rows,
(B, N) controller masks, (B, C) column-signature latency classes, (B, E)
λeff folds — through the SAME compiled engines, so one compile runs B
distinct randomized fault scenarios simultaneously.  The auto-reframe
guard then trips and rotates draws INDIVIDUALLY: the per-chunk trigger
is evaluated per draw, and only tripping rows receive a rotation
(untripped rows keep their λeff bit-exactly and log a zero shift row).
Per-draw LinkDrop/LinkRestore victims change the adjacency itself and
run on the segment-sum or sparse engines (the dense (C, N, N) stacks
are shared across draws).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.controller import ControllerConfig
from repro.core.envelopes import (laplacian, laplacian_pinv,
                                  reframe_guard_margins)
from repro.core.frame_model import (EB_INIT, LinkParams, SimConfig,
                                    _convergence_time, broadcast_gain,
                                    simulate, simulate_ensemble)
from repro.core.reframing import (ReframePolicy, edge_occupancy,
                                  node_net_occupancy, shift_assignment)
from repro.core.topology import Topology
from repro.kernels.api import resolve_options
from repro.kernels.bittide_sparse import ell_tables
from repro.kernels.bittide_step import (SUBLANE, TILE, fused_vmem_bytes,
                                       incidence, select_engine,
                                       sparse_panel, sparse_vmem_bytes,
                                       tiled_vmem_bytes)
from repro.kernels.ops import (_auto_interpret, _fold_index, _fused_engine,
                               _host_watermarks, _lamsum_host, _pad_batch,
                               _pad_gain, _pad_table_rows, _perstep_engine,
                               _sparse_engine, latency_classes)
from repro.kernels.period import Pulses
from repro.telemetry import (NULL_TRACE, Watermarks, coerce_trace,
                             compile_stats)
from repro.telemetry.api import resolve_telemetry

from . import plan as plans
from .compiler import CompiledScenario, compile_scenario
from .events import Scenario
from .plan import SegmentPlan, same_bits

__all__ = ["AppliedReframe", "ScenarioResult", "run_scenario"]

_DENSE_ENGINES = ("auto", "fused", "tiled", "per-step")


def _put(tr, x: np.ndarray):
    """Place a host array on the device, counted in ``h2d_bytes``."""
    tr.count("h2d_bytes", x.nbytes)
    return jnp.asarray(x)


def _fetch(tr, x) -> np.ndarray:
    """Read a device array back, counted in ``d2h_bytes`` and
    ``d2h_reads``."""
    tr.count("d2h_bytes", x.nbytes)
    tr.count("d2h_reads", 1)
    return np.asarray(x)


def _fetch_watermarks(tr, wm_dev, num_records: int, b: Optional[int],
                      n: int) -> Watermarks:
    """:func:`_host_watermarks`, its reads counted in ``d2h_bytes`` and
    ``d2h_reads``."""
    tr.count("d2h_bytes", sum(x.nbytes for x in wm_dev))
    tr.count("d2h_reads", len(wm_dev))
    return _host_watermarks(wm_dev, num_records, b, n)


@functools.partial(jax.jit, static_argnames=("b", "n"))
def _unpad(xs, b: int, n: int):
    """Slice each (..., B_pad, N_pad) array of ``xs`` to (..., b, n) and
    pack the slices, bit-cast to uint32, into one flat buffer.

    The kernel lanes pad N to ``TILE`` lanes and B to ``SUBLANE`` rows:
    an 8-node fabric's record is 1/16 data.  Sliced here, only the data
    crosses to the host, in one transfer.
    """
    return jnp.concatenate([
        jax.lax.bitcast_convert_type(x[..., :b, :n], jnp.uint32).ravel()
        for x in xs])


def _fetch_unpadded(tr, xs, b: int, n: int) -> List[np.ndarray]:
    """Host copies of ``xs`` (32-bit device arrays, padded on their last
    two axes) cut to (..., b, n), read back in one transfer."""
    buf = np.asarray(_unpad(tuple(xs), b=b, n=n))
    tr.count("d2h_bytes", buf.nbytes)
    tr.count("d2h_reads", 1)
    out, at = [], 0
    for x in xs:
        shape = x.shape[:-2] + (min(b, x.shape[-2]), min(n, x.shape[-1]))
        size = int(np.prod(shape))
        out.append(buf[at:at + size].view(x.dtype).reshape(shape))
        at += size
    return out


def _read_chunk(tr, out, b: int, n: int, chunk: int, stop: int):
    """A batched kernel lane's chunk on the host, read in one transfer.

    Returns ``(trips, tstar, valid, freq, beta, wm)``: the (b,) in-kernel
    guard trip records and their earliest (None and ``chunk`` with the
    guard off), the records that count (to the earliest trip, else to
    ``stop``), and those records as the (b, valid, n) ν stream in ppm,
    the (b, valid, n) β stream and the chunk's :class:`Watermarks` (each
    None unless the chunk recorded it)."""
    guard = out.guard_state is not None
    xs = (((out.guard_state,) if guard else ()) + (out.freq,)
          + ((out.beta,) if out.beta is not None else ())
          + tuple(out.watermarks or ()))
    host = iter(_fetch_unpadded(tr, xs, b, n))
    trips = next(host)[:, 0] if guard else None
    tstar = int(trips.min()) if guard else chunk
    valid = min(tstar, stop) + 1
    freq = next(host)[:valid].transpose(1, 0, 2) * 1e6
    beta = (next(host)[:valid].transpose(1, 0, 2)
            if out.beta is not None else None)
    wm = (_host_watermarks(tuple(host), valid, b, n)
          if out.watermarks is not None else None)
    return trips, tstar, valid, freq, beta, wm


def _guard_band_cols(b_pad: int, b: int, target: float, guard_rows,
                     tr=NULL_TRACE):
    """Padded (B_pad, 1) f32 in-kernel guard-band columns.

    Padding draws get an unbounded band (their zero state must never trip
    the shared early-exit freeze for the real draws)."""
    glo = np.full((b_pad, 1), -1e30, np.float32)
    ghi = np.full((b_pad, 1), 1e30, np.float32)
    glo[:b, 0] = target - guard_rows
    ghi[:b, 0] = target + guard_rows
    return _put(tr, glo), _put(tr, ghi)


@dataclasses.dataclass(frozen=True)
class AppliedReframe:
    """One pointer rotation the runner spliced into a scenario.

    record: global record index the rotation precedes (the shift is live
      from this record on); time: the same boundary in seconds.
    shift: integer read-pointer shifts in frames — (E,), or (B, E) when a
      batched run's draws rotated independently.  Δλ per edge equals the
      shift exactly (the frame-rotation invariant).
    auto: True for guard-band splices, False for explicit Reframe events.
    guard_latency: records of exposure between the guard crossing and the
      splice — 1 on the kernel lanes (the in-kernel guard freezes the
      chunk at the trip record, so the rotation lands one record period
      after the crossing), ``chunk − crossing_offset`` on the
      host-inspected segment-sum lane (the trip is only visible once the
      chunk returns), 0 for explicit Reframe events.
    """

    record: int
    time: float
    shift: np.ndarray
    auto: bool
    guard_latency: int = 0


@dataclasses.dataclass
class ScenarioResult:
    """Concatenated telemetry + final state of a scenario run.

    ``freq_ppm`` is (T, N) for a single run or (B, T, N) for an ensemble.

    ``beta`` is the occupancy telemetry in *frames* (empty when β
    recording is off):

    * segment-sum engine — per-edge, (T, E) / (B, T, E);
    * dense/sparse Pallas lanes with ``record_beta=True`` — in-kernel
      per-node net occupancy Σ_{e→i} w_e·β_e, (T, N) / (B, T, N).
      Dropped links (weight 0) leave the aggregation, so the stream
      covers live links only.

    ``lam`` is the (S, E) logical-latency table per segment —
    ``rint(EB_INIT + λeff + ω·l)`` with draw-0 values when λeff is
    per-draw — whose successive differences are the Table-2 latency
    shifts.  Rows are segment-START snapshots: rotations
    ``auto_reframe`` splices mid-segment appear in ``reframes`` and in
    :attr:`lam_final`, not in ``lam`` (graph-mode rotations conserve
    every RTT, so ``rtt()`` is unaffected either way).
    """

    freq_ppm: np.ndarray
    beta: np.ndarray
    times: np.ndarray
    psi: np.ndarray
    nu: np.ndarray
    c_state: dict
    lam: np.ndarray
    lam_eff: np.ndarray
    segment_records: np.ndarray
    segment_times: np.ndarray
    topo: Topology
    links: LinkParams
    ctrl: ControllerConfig
    cfg: SimConfig
    compiled: CompiledScenario
    engine: str
    tile_j: int
    chunk_records: int
    num_launches: int
    # Pointer rotations spliced into the run (explicit Reframe events and
    # auto_reframe guard trips), in record order.
    reframes: List[AppliedReframe] = dataclasses.field(default_factory=list)
    # In-kernel O(N) excursion aggregates (``record_watermarks=True``) —
    # chunk-merged across the whole run, (N,)/(B, N) — else None.
    watermarks: Optional[Watermarks] = None
    # The flight-recorder RunTrace when the run was traced, else None.
    trace: object = None

    @property
    def scenario(self) -> Scenario:
        return self.compiled.scenario

    @property
    def total_reframe_shift(self) -> np.ndarray:
        """(E,) (or (B, E)) accumulated pointer shift over all rotations —
        the net λ the run traded for buffer headroom (zeros if none)."""
        total = np.zeros(self.topo.num_edges, np.int64)
        for r in self.reframes:
            total = total + np.asarray(r.shift, np.int64)
        return total

    def convergence_time(self, band_ppm: float = 1.0,
                         after_s: float = 0.0) -> float:
        """First recorded time >= after_s from which the frequency band
        stays within band_ppm — re-settling time when measured after an
        event.  Single-run results only (index draws for ensembles)."""
        if self.freq_ppm.ndim != 2:
            raise ValueError("convergence_time on an ensemble result: "
                             "slice a draw first (freq_ppm[b])")
        sel = self.times >= after_s
        spread = (self.freq_ppm[sel].max(axis=1)
                  - self.freq_ppm[sel].min(axis=1))
        return _convergence_time(spread, self.times[sel], band_ppm)

    @property
    def lam_final(self) -> np.ndarray:
        """(E,) logical latencies at the END of the run.

        Unlike ``lam[-1]`` (a segment-START snapshot), this is computed
        from the final λeff and therefore includes every rotation
        ``auto_reframe`` spliced mid-segment."""
        return _lam_table(self.lam_eff,
                          self.compiled.segments[-1].latency_s,
                          self.cfg.omega_nom)

    def rtt(self, seg: int = -1) -> np.ndarray:
        """(E,) round-trip logical latency table of one segment (start)."""
        lam = self.lam[seg]
        return lam + lam[self.topo.reverse_edge_index()]

    def lam_shift(self, seg_a: int = 0, seg_b: int = -1) -> np.ndarray:
        """(E,) per-edge logical-latency shift between two segments."""
        return self.lam[seg_b] - self.lam[seg_a]


def _lam_table(lam_eff, lat_s, omega_nom: float) -> np.ndarray:
    """(E,) logical latencies λ = rint(EB_INIT + λeff + ω·l), draw 0."""
    le = np.asarray(lam_eff, np.float64)
    ls = np.asarray(lat_s, np.float64)
    if le.ndim == 2:
        le = le[0]
    if ls.ndim == 2:
        ls = ls[0]
    return np.rint(EB_INIT + le + ls * omega_nom).astype(np.int64)


def _apply_reestablish(lam_eff, edges, beta0_base, psi, nu, lat_frames,
                       topo: Topology):
    """Recompute λeff of ``edges`` so β(t+) equals the β0 setpoint.

    Solves ψ_src − ν_src·ω·l + λeff − ψ_dst = β0 against the live state;
    promotes λeff to per-draw (B, E) when the state is batched (each
    draw's clocks re-establish at different phases).

    ``edges`` is a shared edge-id tuple, or — per-draw victims from a
    chaos campaign — a tuple of B per-row tuples, in which case each
    draw's rows re-establish independently against its own state.
    """
    psi = np.asarray(psi, np.float64)
    nu = np.asarray(nu, np.float64)
    lam_eff = np.asarray(lam_eff, np.float64)
    if edges and isinstance(edges[0], tuple):
        rows = psi.shape[0]
        if lam_eff.ndim == 1:
            lam_eff = np.tile(lam_eff, (rows, 1))
        lat2 = np.broadcast_to(np.asarray(lat_frames, np.float64),
                               lam_eff.shape)
        beta2 = np.broadcast_to(np.asarray(beta0_base, np.float64),
                                lam_eff.shape)
        for bi, row in enumerate(edges):
            if row:
                lam_eff[bi] = _apply_reestablish(
                    lam_eff[bi], row, beta2[bi], psi[bi], nu[bi], lat2[bi],
                    topo)
        return lam_eff
    if psi.ndim == 2 and lam_eff.ndim == 1:
        lam_eff = np.tile(lam_eff, (psi.shape[0], 1))
    idx = list(edges)
    src = np.asarray(topo.src)[idx]
    dst = np.asarray(topo.dst)[idx]
    target = np.asarray(beta0_base, np.float64)[..., idx]
    lf = np.asarray(lat_frames, np.float64)[..., idx]
    lam_eff[..., idx] = (target - psi[..., src] + nu[..., src] * lf
                         + psi[..., dst])
    return lam_eff


def _rotation_shifts(topo: Topology, lam_eff, psi, nu, lat_frames, edge_w,
                     mode: str, target: float, edges=None, explicit=None,
                     lap_pinv=None, rows_mask=None):
    """Resolve a pointer rotation against the live state.

    Args:
      lam_eff: live λeff fold, (E,) or per-draw (B, E) frames.
      psi, nu: live state, (N,) or (B, N) (exact threaded values — every
        engine computes identical shifts from them).
      lat_frames: physical latencies in frames, (E,) or (B, E).
      mode/target/edges/explicit: the rotation spec — explicit integer
        shifts, or state-computed "per-edge" (independent recentering to
        ``target``) / "graph" (RTT-conserving potential assignment from
        the per-node net occupancy) shifts.
      rows_mask: optional (B,) bool — rotate only these draws (the
        auto-reframe guard passes its per-draw trip vector); untripped
        rows keep their λeff and report zero shift.

    Returns ``(lam_eff_new, shift)``.  λeff is promoted to per-draw only
    when the shifts are state-dependent and the state is batched
    (explicit shifts stay shared across draws).
    """
    lam = np.asarray(lam_eff, np.float64)
    e = topo.num_edges
    idx = list(range(e)) if edges is None else list(edges)
    if explicit is not None:
        sh = np.zeros(e, np.int64)
        sh[idx] = np.broadcast_to(np.asarray(explicit, np.int64), (len(idx),))
        return lam + sh, sh
    psi = np.asarray(psi, np.float64)
    nu = np.asarray(nu, np.float64)
    batched = psi.ndim == 2
    if batched and lam.ndim == 1:
        lam = np.tile(lam, (psi.shape[0], 1))
    rows = psi.shape[0] if batched else 1
    lam_rows = lam.reshape(rows, e)
    psi_rows = psi.reshape(rows, -1)
    nu_rows = nu.reshape(rows, -1)
    lat_rows = np.broadcast_to(np.asarray(lat_frames, np.float64),
                               (rows, e))
    if rows_mask is not None:
        rows_mask = np.broadcast_to(
            np.asarray(rows_mask, bool).reshape(-1), (rows,))
    shifts = np.zeros((rows, e), np.int64)
    for bi in range(rows):
        if rows_mask is not None and not rows_mask[bi]:
            continue
        beta = edge_occupancy(topo, psi_rows[bi], nu_rows[bi], lat_rows[bi],
                              lam_rows[bi])
        # The ONE shift-assignment rule (shared with reframe_state);
        # the auto path reuses the guard's cached Laplacian pinv.
        shifts[bi] = shift_assignment(topo, beta, edge_w, mode, target,
                                      edges=edges, lap_pinv=lap_pinv)[1]
    lam_new = lam_rows + shifts
    if not batched:
        return lam_new[0], shifts[0]
    return lam_new, shifts


class _DenseStacks:
    """Per-segment dense adjacency stacks, built once per segment plan.

    ``a[si]`` is the device-resident (C, N_pad, N_pad) float32 adjacency
    of segment ``si`` over the scenario's global latency-class axis.
    Identical parameter sets are deduped (a swap-back event reuses the
    original device buffer), so each unique stack is built on the device
    exactly once per plan however many chunks and calls replay it.
    ``lam_dummy`` is a shared zero (C, 1, 1) placeholder for the
    fused/tiled engines' unused λeff argument (dead in the Pallas jaxpr
    — those kernels fold λeff via the traced ``lamsum`` rows instead —
    so it only needs to exist, not to be full-size; a real (C, N_pad,
    N_pad) zeros stack would double the device footprint at Fig-18
    scale for nothing).
    """

    def __init__(self, a: List, lam_dummy, classes, n_pad: int,
                 class_rows=None, inv=None):
        self.a = a
        self.lam_dummy = lam_dummy
        self.classes = classes          # (C,) shared class values, or None
        self.class_rows = class_rows    # (B, C) per-draw values, or None
        self.inv = inv                  # per-segment (E,) edge→class maps
        self.n_pad = n_pad
        self.num_unique = len({id(x) for x in a})
        # Integer readout's one-hot edge matrices (``Incidence``) by
        # (class map, edge weights), made by the first call that reads
        # whole frames (:func:`_incidence_input`).
        self.incidence = {}


@functools.partial(jax.jit, static_argnames=("c", "n_pad"))
def _scatter_stack(edges, c: int, n_pad: int):
    """(C, N_pad, N_pad) float32 adjacency from one edge list, on device.

    ``edges`` is the (4, E) int32 (class, dst, src, weight) of every
    edge, the float32 weight bit-cast into the last row so the list
    crosses in one transfer.  The zeros are made inside the jit, so XLA
    scatters in place into one fresh buffer.  E is fixed for a fabric
    (dropped links keep weight 0), so one compile serves every call.

    The scatter runs in the TPU's (8, 128) tile order, into a (C,
    N_pad/8, N_pad/128, 8, 128) buffer whose transpose back to (C, N_pad,
    N_pad) is a bitcast of the tiled layout.  Scattered straight into
    (C, N_pad, N_pad), XLA scatters a flat buffer and then relays it out,
    holding a second N² buffer.
    """
    cls, dst, src = edges[0], edges[1], edges[2]
    w = jax.lax.bitcast_convert_type(edges[3], jnp.float32)
    t = jnp.zeros((c, n_pad // SUBLANE, n_pad // TILE, SUBLANE, TILE),
                  jnp.float32)
    t = t.at[cls, dst // SUBLANE, src // TILE, dst % SUBLANE,
             src % TILE].add(w)
    return t.transpose(0, 1, 3, 2, 4).reshape(c, n_pad, n_pad)


def _build_dense_stacks(topo: Topology, comp, cfg: SimConfig,
                        tr=NULL_TRACE) -> _DenseStacks:
    """Build every segment's (C, N_pad, N_pad) A stack up front.

    The host keeps only what is O(E): each segment's edge→class map and
    edge weights, deduped on their bytes.  Each unique set uploads its
    (4, E) edge list and :func:`_scatter_stack` scatters it into a fresh
    device buffer.

    The result equals :func:`repro.kernels.densify` cell for cell
    whenever each (class, dst, src) cell receives one edge or its
    weights sum exactly in float32 (0/1 weights, so every fabric here).
    Parallel edges with fractional weights may accumulate in another
    order, so such a cell can differ by one float32 ulp.

    Under per-draw column-signature latency classes (chaos campaigns) the
    compiler has already assigned every segment's edges to the global
    class axis (``comp.seg_inv``); the A scatter is identical — the class
    *membership* of an edge is shared across draws even when the class
    *values* differ per draw.
    """
    per_draw = comp.per_draw_classes
    if per_draw is not None:
        classes = None
        c = per_draw.shape[1]
    else:
        classes = np.asarray(comp.lat_classes, np.float64)
        c = len(classes)
    n_pad = ((topo.num_nodes + TILE - 1) // TILE) * TILE
    by_key, out, inv_list = {}, [], []
    for si, seg in enumerate(comp.segments):
        if per_draw is not None:
            inv = np.asarray(comp.seg_inv[si], np.int64)
        else:
            lat_frames = (np.asarray(seg.latency_s, np.float64)
                          * cfg.omega_nom)
            _, inv = latency_classes(lat_frames, lat_classes=classes)
            inv = np.asarray(inv, np.int64)
        w = np.asarray(seg.edge_w, np.float64)
        inv_list.append(inv)
        key = (inv.tobytes(), w.tobytes())
        if key not in by_key:
            edges = np.stack([inv.astype(np.int32), topo.dst, topo.src,
                              w.astype(np.float32).view(np.int32)])
            with tr.span("segment.upload"):
                tr.count("h2d_bytes", edges.nbytes)
                edges_d = jax.device_put(edges)
            by_key[key] = _scatter_stack(edges_d, c=c, n_pad=n_pad)
        out.append(by_key[key])
    dummy = np.zeros((c, 1, 1), np.float32)
    with tr.span("segment.upload"):
        tr.count("h2d_bytes", dummy.nbytes)
        lam_dummy = jax.device_put(dummy)
    return _DenseStacks(out, lam_dummy, classes, n_pad,
                        class_rows=per_draw, inv=inv_list)


class _SparseTables:
    """Per-segment ELL slot tables, built once per segment plan.

    The (K, N_pad) neighbor table is topology-determined and shared by
    every segment; ``latf[si]`` / ``w[si]`` are segment ``si``'s per-edge
    latency (frames) and weight slot tables ((R, K, N_pad), R ∈ {1, B}),
    deduped on byte content so swap-back segments reuse one device
    buffer — the sparse analogue of :class:`_DenseStacks`.  Dropped
    links keep their slot with weight 0, so K (and every traced shape)
    is constant across the scenario: one compile serves all segments.
    """

    def __init__(self, nbr, latf: List, w: List, n_pad: int):
        self.nbr = nbr
        self.latf = latf
        self.w = w
        self.k = int(nbr.shape[0])
        self.n_pad = n_pad
        self.num_unique = len({id(x) for x in latf})


def _build_sparse_tables(topo: Topology, comp, cfg: SimConfig,
                         tile: int = TILE, tr=NULL_TRACE) -> _SparseTables:
    """Build every segment's slot tables up front (deduped, one device
    placement per unique (latency, weight) parameter set)."""
    n_pad = ((topo.num_nodes + tile - 1) // tile) * tile
    nbr = None
    by_key, latf_list, w_list = {}, [], []
    for seg in comp.segments:
        lat_f = np.asarray(seg.latency_s, np.float64) * cfg.omega_nom
        w_np = np.asarray(seg.edge_w, np.float64)
        key = (lat_f.tobytes(), w_np.tobytes())
        if key not in by_key:
            tabs = ell_tables(topo, lat_f, edge_w=w_np, n_pad=n_pad)
            with tr.span("segment.upload"):
                if nbr is None:
                    tr.count("h2d_bytes", tabs[0].nbytes)
                    nbr = jax.device_put(tabs[0])
                tr.count("h2d_bytes", tabs[1].nbytes + tabs[2].nbytes)
                by_key[key] = (jax.device_put(tabs[1]),
                               jax.device_put(tabs[2]))
        latf_list.append(by_key[key][0])
        w_list.append(by_key[key][1])
    return _SparseTables(nbr, latf_list, w_list, n_pad)


def _lam_stack(topo: Topology, inv: np.ndarray, lam_eff_row, edge_w,
               c: int, n_pad: int, tr=NULL_TRACE):
    """(C, N_pad, N_pad) λeff tensor for one draw on the per-step lane.

    The same per-edge w·λeff scatter ``densify`` performs (float32
    accumulation included, so shared-class scenarios stay bit-identical
    to the old densify-based path), but driven by a precomputed global
    edge→class map — which, under per-draw column-signature classes, is
    the only form the class assignment exists in.
    """
    lam = np.zeros((c, n_pad, n_pad), np.float32)
    dst = np.asarray(topo.dst, np.int64)
    src = np.asarray(topo.src, np.int64)
    w = (np.ones(topo.num_edges, np.float64) if edge_w is None
         else np.asarray(edge_w, np.float64))
    np.add.at(lam, (inv, dst, src),
              np.asarray(lam_eff_row, np.float64) * w)
    return _put(tr, lam)


class _Inputs(NamedTuple):
    """One segment's draw-independent kernel-lane inputs, kept in the
    fabric's :class:`SegmentPlan`."""
    engine: str        # the lane that runs: the result's and spans' label
    tile: int          # its panel width: tile_j, or tile_i on sparse
    dispatch: dict     # the engine_dispatch event's fields
    b_pad: int
    n_pad: int
    dev: dict          # device arrays, by the engine argument they feed
    gains: tuple       # (kp, β_off) as (B,) float32 host rows


def _kept(plan: SegmentPlan, key, make):
    """``plan.shared[key]``, made by ``make()`` on first use."""
    if key not in plan.shared:
        plan.shared[key] = make()
    return plan.shared[key]


def _common_inputs(plan: SegmentPlan, seg, ctrl: ControllerConfig, b: int,
                   n: int, b_pad: int, n_pad: int, tr):
    """The controller's padded mask and its kp / β_off columns on the
    device, made once per batch key (the mask once per distinct mask), and
    the gains as (B,) float32 host rows."""
    mask_np = np.asarray(seg.ctrl_mask, np.float32)

    def mask():
        if mask_np.ndim == 2:
            # Per-draw holdover victims: (B, N) → padded rows (padding
            # rows keep the controller enabled; their state is inert).
            pad = np.ones((b_pad, n_pad), np.float32)
            pad[:b, :n] = mask_np
        else:
            pad = np.ones((n_pad,), np.float32)
            pad[:n] = mask_np
        return _put(tr, pad)

    def gains():
        host = (broadcast_gain(ctrl.kp, b),
                broadcast_gain(ctrl.beta_off, b, "beta_off"))
        tr.count("h2d_bytes", 2 * b_pad * 4)
        return host, tuple(_pad_gain(g, b_pad) for g in host)

    host, (kp_j, boff_j) = _kept(plan, "gains", gains)
    mask_j = _kept(plan, ("mask", mask_np.shape, mask_np.tobytes()), mask)
    return dict(mask=mask_j, kp=kp_j, boff=boff_j), host


def _law_refusal(lane: str, why: str = "") -> ValueError:
    """The refusal of a lane that lacks FINC/FDEC or integer readout."""
    return ValueError(
        f"FINC/FDEC control and integer buffer readout (quantize_beta) run "
        f"on the fused lane and on the segment-sum engine; the {lane} lane "
        f"implements the proportional controller with continuous readout"
        + why)


def _dense_inputs(plan: SegmentPlan, seg, si: int, ctrl: ControllerConfig,
                  b: int, n: int, engine: str, variant: dict, law: dict,
                  tr=NULL_TRACE) -> _Inputs:
    """Segment ``si``'s draw-independent dense-lane inputs: its A stack
    (see :class:`_DenseStacks`), the engine and panel width ``variant``
    (the kernel's ``record_*`` flags) and ``law`` (integer readout's
    padded edge count and the FINC/FDEC flag, which only the fused lane
    runs) select, the padded latency-class rows, mask and gains."""
    stacks = plan.stacks
    a = stacks.a[si]
    n_pad, c = stacks.n_pad, int(a.shape[0])
    b_pad = ((b + SUBLANE - 1) // SUBLANE) * SUBLANE
    if engine == "auto":
        chosen, tj = select_engine(b_pad, n_pad, c, **variant, **law)
    elif engine == "per-step":
        chosen, tj = "per-step", 0
    elif engine == "tiled":
        chosen, tj = "tiled", select_engine(b_pad, n_pad, c, **variant)[1]
    else:
        chosen, tj = "fused", n_pad
    if chosen != "fused" and (law["edges"] or law["pulses"]):
        raise _law_refusal(chosen, (
            f" (dispatch chose it: the fused working set does not fit at "
            f"B_pad={b_pad}, N_pad={n_pad}, C={c})"))
    if chosen == "fused":
        vmem_est = fused_vmem_bytes(b_pad, n_pad, c, **variant, **law)
    elif chosen == "tiled":
        vmem_est = tiled_vmem_bytes(b_pad, n_pad, c, tj, **variant)
    else:   # per-step: one double-buffered (C, TILE, TILE) tile
        vmem_est = 2 * 4 * c * TILE * TILE

    def lat():
        if stacks.class_rows is not None:
            # Per-draw class values (chaos campaigns): draw bi's row.
            pad = np.empty((b_pad, c), np.float32)
            pad[:b] = stacks.class_rows
            pad[b:] = stacks.class_rows[0]
        else:
            pad = np.broadcast_to(
                np.asarray(stacks.classes, np.float32)[None, :], (b_pad, c))
        return _put(tr, np.ascontiguousarray(pad))

    dev, gains = _common_inputs(plan, seg, ctrl, b, n, b_pad, n_pad, tr)
    dev.update(a=a, lat=_kept(plan, "lat", lat))
    fields = dict(engine=chosen, tile_j=int(tj), b_pad=int(b_pad),
                  n_pad=int(n_pad), c=c, vmem_est_bytes=int(vmem_est))
    if law["edges"]:
        fields["e_pad"] = int(law["edges"])
    return _Inputs(chosen, tj, fields, b_pad, n_pad, dev, gains)


def _incidence_input(plan: SegmentPlan, topo: Topology, seg, si: int,
                     tr=NULL_TRACE):
    """Segment ``si``'s one-hot edge matrices for integer readout on the
    device (``repro.kernels.bittide_step.Incidence``), kept with the
    fabric's stacks and made once per (class map, edge weights)."""
    stacks = plan.stacks
    inv = stacks.inv[si]
    w = np.asarray(seg.edge_w, np.float64)
    key = (inv.tobytes(), w.tobytes())
    if key not in stacks.incidence:
        host = incidence(topo.src, topo.dst, inv, w,
                         int(stacks.a[si].shape[0]), stacks.n_pad)
        stacks.incidence[key] = type(host)(*(_put(tr, x) for x in host))
    return stacks.incidence[key]


def _lam_edges_input(plan: SegmentPlan, lam_eff, b_pad: int, e_pad: int,
                     tr=NULL_TRACE):
    """The per-edge λeff integer readout reads, on the device: (1, E_pad)
    shared or (B_pad, E_pad) per draw, in float32 as the segment-sum
    engine takes it.  The fabric's own β0 is kept in the plan."""
    lam = np.asarray(lam_eff, np.float64)

    def pad():
        rows = lam.reshape(-1, lam.shape[-1])
        out = np.zeros((1 if rows.shape[0] == 1 else b_pad, e_pad),
                       np.float32)
        out[:rows.shape[0], :rows.shape[1]] = rows
        return _put(tr, out)

    if not same_bits(plan.lam0, lam):
        return pad()
    return _kept(plan, "lam_edges", pad)


def _sparse_inputs(plan: SegmentPlan, seg, si: int, ctrl: ControllerConfig,
                   b: int, n: int, variant: dict,
                   tr=NULL_TRACE) -> _Inputs:
    """Segment ``si``'s draw-independent sparse-lane inputs: its slot
    tables padded to B_pad rows, the node-panel width for ``variant``,
    the padded mask and gains."""
    tables = plan.tables
    n_pad = tables.n_pad
    b_pad = ((b + SUBLANE - 1) // SUBLANE) * SUBLANE
    latf = _kept(plan, ("latf", id(tables.latf[si])),
                 lambda: _pad_table_rows(tables.latf[si], b_pad))
    w = _kept(plan, ("w", id(tables.w[si])),
              lambda: _pad_table_rows(tables.w[si], b_pad))
    rows_t = max(latf.shape[0], w.shape[0])
    # Falls back to TILE and lets the kernel's own VMEM check raise.
    ti = sparse_panel(b_pad, n_pad, tables.k, rows_t, **variant) or TILE
    dev, gains = _common_inputs(plan, seg, ctrl, b, n, b_pad, n_pad, tr)
    dev.update(latf=latf, w=w)
    fields = dict(engine="sparse", tile_i=int(ti), b_pad=int(b_pad),
                  n_pad=int(n_pad), k=int(tables.k),
                  vmem_est_bytes=sparse_vmem_bytes(b_pad, n_pad, tables.k,
                                                   ti, rows_t, **variant))
    return _Inputs("sparse", ti, fields, b_pad, n_pad, dev, gains)


def _lamsum_input(plan: SegmentPlan, topo: Topology, si: int, lam_eff,
                  edge_w, b: int, b_pad: int, n_pad: int, tr=NULL_TRACE):
    """Segment ``si``'s (B_pad, N_pad) λeff fold Σ_{e→i} w_e·λeff_e on
    the device, per draw when λeff or the edge weights are.

    The fold of the fabric's own β0 (``plan.lam0``) is kept in the plan,
    made once per edge-weight vector.  Any other λeff — per draw after a
    re-establish or a rotation — folds per call, in one bincount over
    the plan's flat index."""
    lam = np.asarray(lam_eff, np.float64)
    rows = b if (lam.ndim == 2 or np.ndim(edge_w) == 2) else 1

    def fold():
        if rows not in plan.index:
            plan.index[rows] = _fold_index(topo.dst, rows, n_pad)
        folded = _lamsum_host(topo, lam if lam.ndim == 2 else lam[None],
                              edge_w, rows, n_pad, plan.index[rows])
        pad = np.zeros((b_pad, n_pad), np.float32)
        pad[:b] = folded
        return _put(tr, pad)

    if not same_bits(plan.lam0, lam):
        return fold()
    w_np = np.asarray(edge_w)
    return _kept(plan, ("fold", si), lambda: _kept(
        plan, ("fold", w_np.shape, w_np.tobytes()), fold))


class _Lane(NamedTuple):
    """One segment prepped on a kernel lane, as the chunk loop runs it."""
    engine: str        # the lane that runs: the result's and spans' label
    tile: int          # its panel width: tile_j, or tile_i on sparse
    dispatch: dict     # the engine_dispatch event's fields
    b_pad: int
    nu_u: object       # (B_pad, N_pad) ν_u on the device: the first ν
    run: Callable      # (ψ, ν, c_est, stop) →
                       # (ψ, ν, c_est, trips, t*, valid, freq, β, wm)


def run_scenario(topo: Topology, links: LinkParams, ctrl: ControllerConfig,
                 ppm_u: np.ndarray, scenario: Scenario,
                 cfg: SimConfig = SimConfig(),
                 engine: Optional[str] = None,
                 chunk_records: Optional[int] = None,
                 compiled: Optional[CompiledScenario] = None,
                 record_beta: Optional[bool] = None,
                 record_watermarks: Optional[bool] = None,
                 auto_reframe=None,
                 trace=None,
                 options=None, telemetry=None) -> ScenarioResult:
    """Run a dynamic-event scenario, chaining one engine across segments.

    Args:
      topo, links, ctrl, cfg: as for :func:`repro.core.simulate`;
        ``links`` provides the t=0 physical parameters (per-draw (B, E)
        links are supported on the segment-sum engine).
      ppm_u: (N,) single run or (B, N) ensemble of oscillator draws —
        scenario events hit every draw at the same times.  When the
        scenario carries per-draw event parameters (chaos campaigns),
        B must equal the scenario's ``num_draws`` and draw ``b`` sees
        exactly the events of ``scenario.draw(b)``.
      scenario: the event list (compiled here unless ``compiled`` given).
      engine: "segment-sum" (default), a dense Pallas lane
        ("auto" | "fused" | "tiled" | "per-step"), or "sparse" (the
        edge-major ELL lane — bounded-degree mega-scale topologies,
        per-draw LinkDrop victims, heterogeneous per-draw links).
        Which lane runs which controller and readout: segment-sum runs
        every ``ctrl.kind`` with either readout (and telemetry noise);
        every kernel lane runs the proportional controller with
        continuous readout; the fused lane also runs FINC/FDEC
        (``kind="discrete"``, its ``c_est`` carried across chunks and
        segments and returned in ``c_state``) and integer readout
        (``cfg.quantize_beta``), which the tiled, sparse and per-step
        lanes refuse — and so does "auto" where dispatch picks one of
        them.  PI, and FINC/FDEC under the reframing guard, run on
        segment-sum alone.
      chunk_records: kernel-launch granularity override; must divide
        every segment's record count.  Default: the compiler's GCD.
      compiled: reuse a previous :func:`compile_scenario` result.
      record_beta: occupancy telemetry.  ``True`` records β on any
        engine — per-edge (T, E) on segment-sum, in-kernel per-node net
        (T, N) on the dense lanes; ``False`` disables it everywhere.
        Default ``None`` keeps back-compat: segment-sum follows
        ``cfg.record_beta`` and the dense lanes stay on their ν-only
        fast path.  The flag is constant across a scenario, so a
        multi-segment run still compiles each engine exactly once.
      record_watermarks: O(N) in-kernel excursion aggregates.  ``True``
        makes the kernel lanes carry per-node max |β| / time-of-peak /
        ν min-max watermarks in VMEM scratch (the segment-sum lane
        derives the identical quantities host-side from its per-edge
        record), chunk-merged into ``ScenarioResult.watermarks`` —
        available with or without a full ``record_beta`` record, which
        is how large sparse runs report peak excursions without one.
      auto_reframe: closed-loop buffer re-centering.  ``True`` (or a
        :class:`repro.core.reframing.ReframePolicy`) closes the
        reframing loop; when the guard trips, the runner splices an
        RTT-conserving graph-mode pointer rotation (computed from the
        live threaded state) and resumes.  The rotation rewrites only
        traced λeff inputs, so the same compiled engine continues
        across every splice; each one is logged in
        ``ScenarioResult.reframes``.  On batched runs the trip decision
        and the rotation are PER DRAW: a drifting draw reframes alone
        while its batchmates' λeff stays untouched (their shift rows
        are zero).  WHERE the guard runs differs by lane:

        * kernel lanes (dense / sparse / per-step) — the guard runs
          INSIDE the engine: every measure pass checks the per-node net
          occupancy against the degree-scaled per-draw band
          ``target ± (depth/2 − margin)`` and freezes the chunk at the
          first tripping record (post-trip records are predicated
          no-ops), so the splice lands ONE record period after the
          crossing (``AppliedReframe.guard_latency == 1``) regardless
          of ``chunk_records``, and the resumed partial chunk re-enters
          the same executable via a traced stop cap (zero recompiles).
          The β record is NOT required on these lanes — the guard reads
          its own in-kernel measurement.
        * segment-sum — the runner inspects each completed chunk's
          per-edge record (folded by destination, then edge-estimated
          through the Laplacian pseudo-inverse) and splices before the
          next chunk; exposure is up to one chunk
          (``guard_latency == chunk − crossing_offset``), so pick
          ``chunk_records`` (and the policy margin) such that one chunk
          of occupancy slew cannot cross from the guard band to the
          buffer wall.  This lane records β internally for the trigger
          even when the result omits it (only the legacy spelling
          ``auto_reframe=... , record_beta=False`` is rejected as
          contradictory).

        Per-draw margins: with ``policy.margin=None`` each draw's
        margin derives from its OWN gain and disturbance bound
        (:func:`repro.core.envelopes.reframe_guard_margins`), so a
        gain-sweep batch no longer shares one margin computed from the
        stiffest draw.
      options: :class:`repro.kernels.EngineOptions` — the typed home of
        ``engine`` / ``chunk_records``.  Explicit ``engine=`` /
        ``chunk_records=`` win over the corresponding fields.
      telemetry: :class:`repro.telemetry.Telemetry` — the typed home of
        ``record_beta`` / ``record_watermarks`` / ``trace`` /
        ``auto_reframe`` (→ ``Telemetry.guard``); each legacy kwarg
        emits a one-per-process :class:`DeprecationWarning` when
        passed.  When neither ``telemetry`` nor ``record_beta`` is
        given, β recording keeps its legacy default (segment-sum
        follows ``cfg.record_beta``; kernel lanes stay ν-only, except
        that a legacy ``auto_reframe=`` request still implies the β
        record for back-compat).
      trace: flight recorder.  ``True`` attaches a fresh
        :class:`repro.telemetry.RunTrace`; an existing ``RunTrace``
        threads this run's events into it (a chaos campaign shares one
        recorder across its phases).  The runner records engine
        dispatches (with the select_engine regime and a VMEM footprint
        estimate), per-chunk engine-launch spans, guard evaluations,
        reframe splices, and the jit-cache delta over the run — all
        host-side bookkeeping, so tracing compiles nothing.

    Returns:
      ScenarioResult with concatenated telemetry, threaded final state,
      and the per-segment logical-latency table.
    """
    if auto_reframe and record_beta is False:
        raise ValueError(
            "auto_reframe inspects the β record; record_beta=False is "
            "contradictory on this legacy spelling (the typed "
            "telemetry=Telemetry(guard=...) runs the guard without "
            "surfacing the record)")
    opts = resolve_options(options, "run_scenario", engine=engine,
                           chunk_records=chunk_records,
                           default_engine="segment-sum")
    beta_explicit = telemetry is not None or record_beta is not None
    tel = resolve_telemetry(
        telemetry, "run_scenario", beta=record_beta,
        watermarks=record_watermarks,
        trace=trace if trace else None,
        guard=auto_reframe if auto_reframe else None)
    tr = coerce_trace(tel.trace, name="run_scenario")
    with tr.span("scenario", engine=opts.engine):
        return _run_scenario(topo, links, ctrl, ppm_u, scenario, cfg,
                             compiled, opts, tel, beta_explicit, tr)


def _run_scenario(topo: Topology, links: LinkParams, ctrl: ControllerConfig,
                  ppm_u, scenario: Scenario, cfg: SimConfig,
                  compiled: Optional[CompiledScenario], opts, tel,
                  beta_explicit: bool, tr) -> ScenarioResult:
    """The body of :func:`run_scenario`, inside its ``scenario`` span."""
    engine = opts.engine
    ppm_u = np.asarray(ppm_u, np.float32)
    single = ppm_u.ndim == 1
    comp = compiled
    if not comp:
        with tr.span("segment.compile"):
            comp = compile_scenario(scenario, topo, links, cfg)
    chunk = opts.chunk_records or comp.chunk_records
    for s in comp.segments:
        if chunk < 1 or s.records % chunk:
            raise ValueError(
                f"chunk_records={chunk} does not divide segment of "
                f"{s.records} records (compiler GCD: {comp.chunk_records})")

    dense = engine in _DENSE_ENGINES
    sparse = engine == "sparse"
    if not dense and not sparse and engine != "segment-sum":
        raise ValueError(f"unknown engine {engine!r}")
    if comp.num_draws is not None and (single
                                       or ppm_u.shape[0] != comp.num_draws):
        raise ValueError(
            f"scenario carries per-draw event parameters for "
            f"B={comp.num_draws} draws; ppm_u must be "
            f"({comp.num_draws}, N), got {ppm_u.shape}")
    if dense:
        if comp.lat_classes is None and comp.per_draw_classes is None:
            raise ValueError(
                "dense scenario engines need shared base links or per-draw "
                "latencies that collapse to few column-signature classes; "
                "fully heterogeneous (B, E) latencies run on the "
                "segment-sum engine" + "".join(
                    "\n  note: " + nt for nt in comp.notes))
        if any(np.asarray(s.edge_w).ndim == 2 for s in comp.segments):
            raise ValueError(
                "per-draw LinkDrop/LinkRestore victims need the "
                "segment-sum or sparse engine (the dense (C, N, N) "
                "adjacency stacks are shared across draws)")
    pulses = ctrl.kind == "discrete"
    if dense or sparse:
        kind = "dense" if dense else "sparse"
        if ctrl.kind == "pi":
            raise ValueError(
                f"{kind} engines implement the proportional controller and, "
                f"on the fused lane, FINC/FDEC; 'pi' runs on the "
                f"segment-sum engine")
        if (pulses or cfg.quantize_beta) and engine not in ("auto", "fused"):
            raise _law_refusal(engine)
        if pulses and tel.guard:
            raise ValueError(
                "the in-kernel reframing guard runs under the proportional "
                "controller; FINC/FDEC with a guard runs on the segment-sum "
                "engine")
        if cfg.telemetry_noise_ppm:
            raise ValueError("telemetry noise is a segment-sum feature")

    # β recording: the typed request wins; with neither telemetry= nor
    # record_beta= passed, segment-sum keeps the cfg.record_beta default
    # and the kernel lanes their ν-only fast path.
    rb_seg = tel.beta if beta_explicit else cfg.record_beta
    rb_dense = tel.beta if beta_explicit else False
    rw = tel.watermarks
    cs0 = dict(compile_stats()) if tr else None

    guard_on = bool(tel.guard)
    policy: Optional[ReframePolicy] = None
    guard_rows = None        # (B,) per-draw trip thresholds (frames/degree)
    if guard_on:
        policy = (tel.guard if isinstance(tel.guard, ReframePolicy)
                  else ReframePolicy())
        b_g = 1 if single else ppm_u.shape[0]
        if not beta_explicit:
            # Legacy auto_reframe= implied the β record; the in-kernel
            # guard no longer needs it (and segment-sum records it
            # internally for the host trigger either way), but keep the
            # record in the RESULT by default so pre-redesign callers
            # still see ScenarioResult.beta.
            rb_seg = rb_dense = True
        with tr.span("guard", name="margins"):
            if policy.margin is None:
                # Per-draw margins: each draw's OWN gain and disturbance
                # bound — one margin computed from the stiffest draw
                # under-guarded the rest of a gain-sweep batch.
                kp_rows = np.asarray(broadcast_gain(ctrl.kp, b_g),
                                     np.float64)
                ppm_rows = np.broadcast_to(
                    np.abs(np.atleast_2d(ppm_u)).max(axis=1), (b_g,))
                dppm_rows = np.zeros(b_g, np.float64)
                for s in comp.segments:
                    d = np.abs(np.asarray(s.dppm, np.float64))
                    dppm_rows = np.maximum(
                        dppm_rows, d.max(axis=1) if d.ndim == 2 else d.max())
                lat_max = max(float(np.asarray(s.latency_s).max())
                              for s in comp.segments) * cfg.omega_nom
                margins = reframe_guard_margins(
                    topo, kp_rows, cfg.dt, cfg.record_every,
                    (ppm_rows + dppm_rows) * 1e-6, lat_max, cfg.omega_nom)
            else:
                margins = np.full(b_g, float(policy.margin))
            guard_rows = np.asarray(policy.guard(margins),
                                    np.float64).reshape(-1)

    readout = "integer" if cfg.quantize_beta else "continuous"
    rec_period = cfg.dt * cfg.record_every
    beta0_base = np.asarray(links.beta0, np.float64)
    lam_eff = np.array(beta0_base, copy=True)
    n = topo.num_nodes
    b = 1 if single else ppm_u.shape[0]
    state = None                 # segment-sum: result object with .psi/.nu
    psi_pad = nu_pad = None      # dense lanes: padded (B_pad, N_pad) state
    c_pad = None                 # and FINC/FDEC's c_est, (B_pad, N_pad)
    freq_chunks, beta_chunks = [], []
    wm_acc: Optional[Watermarks] = None
    lam_rows, launches = [], 0
    reframes: List[AppliedReframe] = []
    guard_cache: dict = {}     # edge_w bytes -> (deg_w, Laplacian pinv)
    gband = None               # padded (B_pad, 1) kernel-lane guard band
    rec_done, total = 0, comp.total_records
    eng_label, tile_j = engine, 0
    interp = _auto_interpret()
    variant = dict(record_beta=bool(rb_dense), record_watermarks=bool(rw),
                   record_guard=guard_on)
    # Integer readout reads E_pad edges a period; FINC/FDEC carries c_est.
    law = dict(edges=(-(-topo.num_edges // TILE) * TILE
                      if cfg.quantize_beta else 0), pulses=pulses)
    # The fabric's segment plan (``repro.scenarios.plan``): all segments'
    # dense adjacency stacks / sparse slot tables, built once per fabric
    # (the chunk loop never re-densifies A or re-scatters slots, and a
    # later call on an equal fabric builds neither), and the segments'
    # padded inputs, made again when the controller, the draw count, the
    # lane or the telemetry variant changes.
    plan = None
    if dense or sparse:
        with tr.span("segment.stacks"):
            plan = plans.lookup(
                (topo, links, cfg, scenario if compiled is None else compiled),
                (engine, ctrl, ppm_u.shape, tuple(variant.values())), tr)
            if plan.lam0 is None:
                plan.lam0 = np.array(links.beta0, np.float64)
            if dense and plan.stacks is None:
                plan.stacks = _build_dense_stacks(topo, comp, cfg, tr=tr)
            if sparse and plan.tables is None:
                plan.tables = _build_sparse_tables(topo, comp, cfg, tr=tr)
    nu_last = None           # (dppm, ν_u on the device) of the last prep

    def live_state():
        """Exact threaded (ψ, ν) — (N,)/(B, N) float host views.  Every
        engine resolves rotations/re-establishments against these, so
        the spliced λeff rewrites agree across lanes to state precision."""
        if state is None and psi_pad is None:
            return (np.zeros_like(ppm_u, np.float64),
                    ppm_u.astype(np.float64) * 1e-6)
        if dense or sparse:
            psi_now, nu_now = _fetch_unpadded(tr, (psi_pad, nu_pad), b, n)
            return (psi_now[0], nu_now[0]) if single else (psi_now, nu_now)
        return state.psi, state.nu

    dt_frames = float(cfg.omega_nom * cfg.dt)

    def guard_kw(stop):
        """The in-kernel guard's traced inputs for a chunk capped at
        record ``stop``."""
        return dict(record_guard=guard_on,
                    guard_lo=gband[0] if guard_on else None,
                    guard_hi=gband[1] if guard_on else None,
                    guard_stop=stop if guard_on else None)

    def batched(launch):
        """The chunk of a lane that launches every draw at once: launch,
        wait, and read the chunk back in one transfer."""
        def run(psi, nu, c_est, stop):
            with tr.span("chunk.dispatch"):
                out = launch(psi, nu, c_est, stop)
            with tr.span("chunk.wait"):
                jax.block_until_ready(out)
            with tr.span("chunk.fetch"):
                return (out.psi, out.nu, out.c_est) + _read_chunk(
                    tr, out, b, n, chunk, stop)
        return run

    def prep_lane(si, seg, lam_seg, ppm_seg) -> _Lane:
        """Segment ``si`` prepped on the kernel lane: the plan's inputs
        for it, with this call's ν_u and λeff fold."""
        nonlocal nu_last
        if si not in plan.inputs:
            plan.inputs[si] = (
                _sparse_inputs(plan, seg, si, ctrl, b, n, variant, tr)
                if sparse else _dense_inputs(plan, seg, si, ctrl, b, n,
                                             engine, variant, law, tr))
        ins = plan.inputs[si]
        dev = ins.dev
        dppm = np.asarray(seg.dppm, np.float32)
        if nu_last is None or not same_bits(nu_last[0], dppm):
            nu_u_j = _pad_batch(np.atleast_2d(ppm_seg), n, ins.n_pad)[0]
            tr.count("h2d_bytes", nu_u_j.nbytes)
            nu_last = (dppm, nu_u_j)
        nu_u_j = nu_last[1]
        lamsum_j = _lamsum_input(plan, topo, si, lam_seg, seg.edge_w, b,
                                 ins.b_pad, ins.n_pad, tr)
        if sparse:
            def launch_sparse(psi, nu, c_est, stop):
                return _sparse_engine(
                    psi, nu, nu_u_j, dev["kp"], dev["boff"], dev["mask"],
                    plan.tables.nbr, dev["latf"], dev["w"], lamsum_j,
                    dt_frames, int(chunk), int(cfg.record_every),
                    int(ins.tile), interp, rb_dense, rw, **guard_kw(stop))

            return _Lane("sparse", ins.tile, ins.dispatch, ins.b_pad,
                         nu_u_j, batched(launch_sparse))
        chosen, tj = ins.engine, ins.tile
        if chosen != "per-step":
            law_j = {}
            if pulses:
                law_j["pulses"] = Pulses(float(ctrl.fs),
                                         int(ctrl.pulses_per_update))
            if law["edges"]:
                law_j.update(
                    edges=_incidence_input(plan, topo, seg, si, tr),
                    lam_edges=_lam_edges_input(plan, lam_seg, ins.b_pad,
                                               law["edges"], tr))

            def launch_dense(psi, nu, c_est, stop):
                return _fused_engine(
                    psi, nu, nu_u_j, dev["kp"], dev["boff"], dev["mask"],
                    dev["a"], plan.stacks.lam_dummy, lamsum_j, dev["lat"],
                    dt_frames, int(chunk), int(cfg.record_every), chosen,
                    int(tj), interp, False, rb_dense, rw, **guard_kw(stop),
                    **law_j, **({"c_est": c_est} if pulses else {}))

            return _Lane(chosen, tj, ins.dispatch, ins.b_pad, nu_u_j,
                         batched(launch_dense))
        # The capability lane consumes the dense λeff tensor directly; its
        # per-period kernel folds lamsum internally from it.  (Rebuilt per
        # segment: λeff is live state under re-establishment events.)  It
        # launches each draw on its own, with its gains as compile keys.
        inv_seg, c = plan.stacks.inv[si], int(dev["a"].shape[0])
        lam = np.asarray(lam_seg, np.float64)
        if lam.ndim == 2:
            lam_list = [_lam_stack(topo, inv_seg, lam[bi], seg.edge_w, c,
                                   ins.n_pad, tr) for bi in range(b)]
        else:
            lam_list = [_lam_stack(topo, inv_seg, lam, seg.edge_w, c,
                                   ins.n_pad, tr)] * b
        kp_np, boff_np = ins.gains
        mask_j, a, lat_j = dev["mask"], dev["a"], dev["lat"]

        def run_perstep(psi, nu, c_est, stop):
            def launch(bi, stop_i):
                return _perstep_engine(
                    psi[bi], nu[bi], nu_u_j[bi],
                    mask_j[bi] if mask_j.ndim == 2 else mask_j, a,
                    lam_list[bi], lat_j[bi], float(kp_np[bi]),
                    float(boff_np[bi]), dt_frames, int(chunk),
                    int(cfg.record_every), interp, False, rb_dense, rw,
                    record_guard=guard_on,
                    guard_lo=(float(policy.target - guard_rows[bi])
                              if guard_on else None),
                    guard_hi=(float(policy.target + guard_rows[bi])
                              if guard_on else None),
                    guard_stop=stop_i if guard_on else None)

            with tr.span("chunk.dispatch"):
                rows = [launch(bi, stop) for bi in range(b)]
            with tr.span("chunk.wait"):
                jax.block_until_ready(rows)
            trips, tstar = None, chunk
            if guard_on:
                with tr.span("chunk.fetch"):
                    trips = np.array([int(_fetch(tr, r.guard_state))
                                      for r in rows])
                tstar = int(trips.min())
            if guard_on and tstar <= stop and bool((trips > tstar).any()):
                # This lane launches draws separately, so the Pallas
                # lanes' global batch freeze needs a host resync: re-run
                # the draws that ran past the earliest trip with the stop
                # cap AT that record — the deterministic prefix lands
                # their state exactly there, through the same executable
                # (the cap is traced).
                with tr.span("chunk.dispatch"):
                    for bi in np.flatnonzero(trips > tstar):
                        rows[int(bi)] = launch(int(bi), int(tstar))
                with tr.span("chunk.wait"):
                    jax.block_until_ready(rows)
            valid = min(tstar, stop) + 1
            with tr.span("chunk.fetch"):
                psi = psi.at[:b].set(jnp.stack([r.psi for r in rows]))
                nu = nu.at[:b].set(jnp.stack([r.nu for r in rows]))
                freq = np.stack([_fetch(tr, r.freq)[:valid, :n]
                                 for r in rows]) * 1e6
                beta = (np.stack([_fetch(tr, r.beta)[:valid, :n]
                                  for r in rows]) if rb_dense else None)
                wm = (Watermarks.stack([_fetch_watermarks(
                    tr, r.watermarks, valid, None, n) for r in rows])
                    if rw else None)
            return psi, nu, c_est, trips, tstar, valid, freq, beta, wm

        return _Lane(chosen, tj, ins.dispatch, ins.b_pad, nu_u_j,
                     run_perstep)

    for si, seg in enumerate(comp.segments):
        lat_frames = np.asarray(seg.latency_s, np.float64) * cfg.omega_nom
        if seg.reestablish:
            with tr.span("segment.splice", segment=si):
                psi_now, nu_now = live_state()
                lam_eff = _apply_reestablish(
                    lam_eff, seg.reestablish, beta0_base, psi_now, nu_now,
                    lat_frames, topo)
        for ev in seg.reframe:
            # Explicit Reframe events: resolved at the boundary against
            # the live state (like re-establishment), applied as a λeff
            # rewrite whose Δλ is exactly the pointer shift.
            with tr.span("reframe", record=int(seg.start_record),
                         auto=False, segment=si):
                psi_now, nu_now = live_state()
                lam_eff, shift = _rotation_shifts(
                    topo, lam_eff, psi_now, nu_now, lat_frames, seg.edge_w,
                    ev.mode, ev.target, edges=ev.edges, explicit=ev.shift)
                reframes.append(AppliedReframe(
                    record=seg.start_record,
                    time=seg.start_record * rec_period, shift=shift,
                    auto=False))
                tr.note(max_shift=int(np.abs(shift).max()))
        with tr.span("segment.prep", segment=si):
            dppm32 = np.asarray(seg.dppm, np.float32)
            ppm_seg = (ppm_u + dppm32 if (single or dppm32.ndim == 2)
                       else ppm_u + dppm32[None])
            if not (dense or sparse):
                links_seg = LinkParams(latency_s=seg.latency_s,
                                       beta0=np.array(lam_eff, copy=True))
            lam_rows.append(_lam_table(lam_eff, seg.latency_s,
                                       cfg.omega_nom))
        if policy is not None:
            # Guard preparation: the dense record is the per-NODE net
            # occupancy, but the buffer wall is per EDGE.  The
            # graph-consistent per-edge estimate inverts the same
            # Laplacian fold the shifts solve — β̂_e = p_src − p_dst with
            # L p = −(net − target·deg) — so the trigger watches exactly
            # the occupancy component a rotation can recenter, at one
            # (T, N) × (N, N) matmul per chunk.  The O(N³) pseudo-inverse
            # is cached on the edge-weight vector: edge_w only changes at
            # LinkDrop/LinkRestore boundaries, so ramp-heavy scenarios
            # (one segment per record) pay it once, not per segment.
            wkey = np.asarray(seg.edge_w, np.float64).tobytes()
            if wkey not in guard_cache:
                with tr.span("guard", name="pinv", segment=si):
                    deg_c = np.zeros(n, np.float64)
                    np.add.at(deg_c, np.asarray(topo.dst),
                              np.asarray(seg.edge_w, np.float64))
                    guard_cache[wkey] = (deg_c, laplacian_pinv(
                        laplacian(topo, np.asarray(seg.edge_w,
                                                   np.float64))))
            deg_w, lap_pinv = guard_cache[wkey]
            src_np, dst_np = np.asarray(topo.src), np.asarray(topo.dst)

            def edge_estimates(net_records):
                """Per-draw per-record max |β̂_e| of (..., T, N) net rows.

                Returns (B_eff, T) — a leading draw axis (ndim 3: draw ×
                record × node) is kept, a single run becomes B_eff=1 —
                so the segment-sum guard trips, and rotates, draws
                INDIVIDUALLY, and the crossing's record offset inside
                the chunk prices ``AppliedReframe.guard_latency``.
                """
                dev = np.asarray(net_records, np.float64) \
                    - policy.target * deg_w
                pot = dev @ lap_pinv.T
                est = np.abs(pot[..., src_np] - pot[..., dst_np])
                return np.atleast_2d(est.max(axis=-1))

        if dense or sparse:
            # Segment prep — ν_u, the λeff fold, the plan's padded inputs —
            # happens ONCE per segment; the chunk loop below replays the
            # jitted engine on device-resident padded state with zero host
            # rebuilds (A and the slot tables were built before the
            # segment loop).
            with tr.span("segment.prep", segment=si):
                lane = prep_lane(si, seg, lam_eff, ppm_seg)
                if psi_pad is None:
                    psi_pad, nu_pad = jnp.zeros_like(lane.nu_u), lane.nu_u
                    # FINC/FDEC starts from c_est = 0, made on the device.
                    c_pad = jnp.zeros_like(lane.nu_u) if pulses else None
            eng_label, tile_j = lane.engine, lane.tile
            tr.event("engine_dispatch", segment=si, **lane.dispatch,
                     controller=ctrl.kind, readout=readout)
            if guard_on and gband is None:
                with tr.span("guard", name="band"):
                    gband = _guard_band_cols(lane.b_pad, b, policy.target,
                                             guard_rows, tr)
            seg_done = 0
            while seg_done < seg.records:
                # Traced stop cap: a post-splice partial chunk keeps the
                # static num_records and no-ops its tail — zero recompiles.
                stop = min(chunk, seg.records - seg_done) - 1
                with tr.span("chunk", engine=lane.engine, segment=si,
                             launch=launches, records=int(stop + 1)):
                    (psi_pad, nu_pad, c_pad, trips, tstar, valid, freq_c,
                     beta_c, wm_c) = lane.run(psi_pad, nu_pad, c_pad, stop)
                    freq_chunks.append(freq_c)
                    if rb_dense:
                        beta_chunks.append(beta_c)
                if rw:
                    wm_acc = wm_c if wm_acc is None else wm_acc.merge(wm_c)
                launches += 1
                seg_done += valid
                rec_done += valid
                tripped_now = guard_on and tstar <= stop
                if guard_on:
                    tr.event("guard_eval", record=int(rec_done),
                             guard=float(guard_rows.min()),
                             tripped=(int(np.count_nonzero(trips == tstar))
                                      if tripped_now else 0))
                if tripped_now and rec_done < total:
                    # In-kernel guard trip: only the draws that tripped AT
                    # the freeze record rotate — a drifting draw must not
                    # perturb its well-behaved batchmates (they keep λeff
                    # bit-exactly and log a zero shift row).
                    with tr.span("reframe", record=int(rec_done), auto=True,
                                 segment=si):
                        psi_now, nu_now = live_state()
                        lam_eff, shift = _rotation_shifts(
                            topo, lam_eff, psi_now, nu_now, lat_frames,
                            seg.edge_w, "graph", policy.target,
                            lap_pinv=lap_pinv, rows_mask=(trips == tstar))
                        reframes.append(AppliedReframe(
                            record=rec_done, time=rec_done * rec_period,
                            shift=shift, auto=True, guard_latency=1))
                        tr.note(max_shift=int(np.abs(shift).max()))
                        # The rotation rewrites only traced inputs (the
                        # lamsum fold / per-step λeff tensors), so the
                        # re-prepped segment replays the SAME compiled
                        # engine — zero recompiles across splices.  On a
                        # segment's final record the next segment's own
                        # prep picks the shifted lam_eff up, so skip the
                        # re-prep there (its outputs would be discarded).
                        if seg_done < seg.records:
                            lane = prep_lane(si, seg, lam_eff, ppm_seg)
            continue

        tr.event("engine_dispatch", segment=si, engine="segment-sum",
                 records=int(seg.records), controller=ctrl.kind,
                 readout=readout)
        for _ in range(seg.records // chunk):
            # Per-launch derived seed: telemetry-noise keys must differ
            # across chunks (exact zeros when noise is off, so splitting
            # stays bit-identical).  Watermarks need the β record even
            # when the caller did not ask for one (rb_seg stays in charge
            # of what the RESULT carries).
            cfg_chunk = dataclasses.replace(
                cfg, steps=chunk * cfg.record_every,
                seed=cfg.seed + 104729 * launches,
                record_beta=rb_seg or rw or guard_on)
            with tr.span("chunk", engine="segment-sum", segment=si,
                         launch=launches, records=int(chunk)):
                if single:
                    res = simulate(topo, links_seg, ctrl, ppm_seg, cfg_chunk,
                                   init=state, edge_w=seg.edge_w,
                                   ctrl_mask=seg.ctrl_mask)
                else:
                    res = simulate_ensemble(topo, links_seg, ctrl, ppm_seg,
                                            cfg_chunk, init=state,
                                            edge_w=seg.edge_w,
                                            ctrl_mask=seg.ctrl_mask)
            state = res
            freq_chunks.append(res.freq_ppm)
            beta_chunks.append(res.beta)
            launches += 1
            rec_done += chunk
            if rw:
                # Host-side watermark fold: the per-edge record's
                # destination aggregation is the same per-node net
                # occupancy the kernel lanes watermark in VMEM.
                net_wm = node_net_occupancy(topo, res.beta, seg.edge_w)
                wm_c = Watermarks.from_record(np.asarray(net_wm),
                                              res.freq_ppm)
                wm_acc = wm_c if wm_acc is None else wm_acc.merge(wm_c)
            if policy is not None and rec_done < total:
                # Host-side trigger: the per-edge record folded by
                # destination, then edge-estimated per draw AND per
                # record — only tripping draws rotate, and the earliest
                # crossing's offset inside the chunk prices the exposure
                # (``guard_latency = chunk − offset``; the kernel lanes'
                # in-kernel guard holds this at 1).
                net = node_net_occupancy(topo, res.beta, seg.edge_w)
                hit = edge_estimates(net) >= guard_rows[:, None]
                tripped = hit.any(axis=1)
                tr.event("guard_eval", record=int(rec_done),
                         guard=float(guard_rows.min()),
                         tripped=int(np.count_nonzero(tripped)))
                if tripped.any():
                    first = int(np.flatnonzero(hit.any(axis=0))[0])
                    with tr.span("reframe", record=int(rec_done), auto=True,
                                 segment=si):
                        lam_eff, shift = _rotation_shifts(
                            topo, lam_eff, res.psi, res.nu, lat_frames,
                            seg.edge_w, "graph", policy.target,
                            lap_pinv=lap_pinv, rows_mask=tripped)
                        reframes.append(AppliedReframe(
                            record=rec_done, time=rec_done * rec_period,
                            shift=shift, auto=True,
                            guard_latency=int(chunk - first)))
                        tr.note(max_shift=int(np.abs(shift).max()))
                        links_seg = LinkParams(
                            latency_s=seg.latency_s,
                            beta0=np.array(lam_eff, copy=True))

    axis = 1 if (dense or sparse or not single) else 0
    freq = np.concatenate(freq_chunks, axis=axis)
    if dense or sparse:
        if single:
            freq = freq[0]
        psi_f, nu_f, *c_f = _fetch_unpadded(
            tr, (psi_pad, nu_pad) + ((c_pad,) if pulses else ()), b, n)
        if rb_dense:
            beta = np.concatenate(beta_chunks, axis=1)
            if single:
                beta = beta[0]
        else:
            beta = np.zeros(freq.shape[:-1] + (0,), np.float32)
        if single:
            psi_f, nu_f, c_f = psi_f[0], nu_f[0], [c[0] for c in c_f]
        c_state = {"c_est": c_f[0]} if pulses else {}
    else:
        beta = (np.concatenate(beta_chunks, axis=axis) if rb_seg
                else np.zeros(freq.shape[:-1] + (0,), np.float32))
        psi_f, nu_f, c_state = state.psi, state.nu, state.c_state

    wm_res = wm_acc
    if wm_res is not None and single and (dense or sparse):
        wm_res = wm_res[0]
    if tr:
        cs1 = compile_stats()
        tr.event("compile_stats", before=cs0, after=cs1,
                 delta={k: cs1[k] - cs0[k] for k in cs1})

    total = comp.total_records
    times = (np.arange(1, total + 1)) * rec_period
    return ScenarioResult(
        freq_ppm=freq, beta=beta, times=times, psi=psi_f, nu=nu_f,
        c_state=c_state, lam=np.stack(lam_rows), lam_eff=lam_eff,
        segment_records=np.array([s.start_record for s in comp.segments]),
        segment_times=np.array([s.start_record * rec_period
                                for s in comp.segments]),
        topo=topo, links=links, ctrl=ctrl, cfg=cfg, compiled=comp,
        engine=eng_label, tile_j=tile_j, chunk_records=chunk,
        num_launches=launches, reframes=reframes,
        watermarks=wm_res, trace=(tr if tr else None))
