"""jit'd wrappers around the Pallas bittide kernels + topology densification.

`densify` converts an edge-list topology into the latency-class dense form
the kernels consume (padding N up to the tile size).  The production entry
points are:

``simulate_fused``
    One synchronization run on the fused multi-period engine: a single
    ``pallas_call`` advances ``steps`` control periods with state carried
    in VMEM scratch across the record grid and ν telemetry decimated
    in-kernel to every ``record_every`` periods.  The adjacency is either
    VMEM-resident ("fused") or streamed from HBM in double-buffered column
    panels ("tiled") — `repro.kernels.bittide_step.select_engine` picks
    per problem size, so Fig-18-scale tori stay on the fast path instead
    of dropping to the per-step kernel.

``simulate_ensemble_dense``
    The batched lane: B independent oscillator draws (Monte Carlo over the
    paper's ±8 ppm envelope) advance together through the same fused
    kernel — the per-period matvec becomes a (B, N) × (N, N) MXU matmul
    and one compile serves B × steps × N node-steps.  ``kp`` / ``beta_off``
    accept per-draw arrays (traced, never compile keys), so a Fig-15-style
    gain sweep batches along B and compiles exactly once.

``simulate_dense``
    Back-compat wrapper (per-period telemetry, single draw); delegates to
    the fused engine.  The old one-``pallas_call``-per-period
    ``lax.scan`` runner survives only as ``simulate_dense_perstep``, the
    benchmark baseline that the fused engine is measured against.

All dense runners return a :class:`DenseResult` — a 2-tuple
``(freq_ppm, psi)`` (unpacks exactly like before) carrying ``.engine`` /
``.tile_j`` dispatch metadata, ``.nu``, the exact final frequencies for
segment chaining, and ``.beta``, the in-kernel per-node net occupancy
telemetry (frames) when ``record_beta=True``.

Scenario plumbing (``repro.scenarios``): ``init=`` seeds the state from
a prior result, ``ctrl_mask=`` gates the controller per node (holdover),
``edge_w=`` drops links from the error aggregation, and ``lat_classes=``
pins the dense latency-class axis so piecewise-constant segments share
one compiled kernel.  The per-node λeff fold ``lamsum`` is likewise a
traced (B, N) input — it is the ONLY λeff the fused/tiled kernels
consume — which is what lets the closed-loop reframing subsystem
(``run_scenario(auto_reframe=...)``) splice read-pointer rotations
(λeff += integer shifts) between record chunks without ever recompiling:
a rotation is a data rewrite of ``lamsum`` (and of the per-step lane's
λeff tensor), never a shape change.  ``links`` may carry per-draw (B, E) parameters —
the dense lane requires a shared class structure (one latency per class
per draw); fully heterogeneous per-draw links run on the segment-sum
lane in ``repro.core.frame_model``.

Interpret mode is a property of the backend, decided in one place
(:func:`_auto_interpret`): off a TPU the kernels run in the Pallas
interpreter, on a TPU the same code compiles to Mosaic and never runs in
the interpreter.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.frame_model import LinkParams, OMEGA_NOM, broadcast_gain
from repro.core.topology import Topology
from repro.telemetry.api import resolve_telemetry
from repro.telemetry.watermarks import Watermarks

from .api import EngineOutputs, resolve_options
from .bittide_sparse import bittide_sparse_pallas, ellify, max_in_degree
from .bittide_step import (SUBLANE, TILE, bittide_fused_pallas,
                           bittide_step_pallas, bittide_tiled_fused_pallas,
                           select_engine, sparse_panel)
from .period import fold_watermarks, live, never_tripped, out_of_band
from .ref import (bittide_dense_multistep_ref, bittide_dense_step_ref,
                  node_occupancy_ref)

__all__ = ["densify", "latency_classes", "bittide_step", "simulate_dense",
           "simulate_dense_perstep", "simulate_fused",
           "simulate_ensemble_dense", "DenseResult"]


# Beyond this many exact latency classes, densify falls back to quantized
# merging (the dense stack is (C, N, N) — C must stay small).
MAX_EXACT_CLASSES = 8


def _auto_interpret() -> bool:
    """Whether the Pallas kernels run in the interpreter: exactly when
    the default backend is not a TPU.  No caller chooses — the
    interpreter never stands in for Mosaic on a TPU, and Mosaic cannot
    run anywhere else."""
    return jax.default_backend() != "tpu"


class DenseResult(tuple):
    """``(freq_ppm, psi)`` pair with engine-dispatch metadata attached.

    Unpacks like the historical 2-tuple; ``.engine`` names the kernel path
    the dispatch heuristic chose (``"fused"`` | ``"tiled"`` |
    ``"per-step"`` | ``"ref"``) and ``.tile_j`` is the adjacency j-panel
    width in nodes (== padded N when the stack is VMEM-resident).

    ``.nu`` carries the exact final relative frequencies (same layout as
    ``psi``) so a result can seed the next run via ``init=`` — the
    scenario runner's segment-chaining contract.  (``freq_ppm[..., -1, :]``
    is ν·1e6 rounded through float32 and does NOT round-trip bitwise.)

    ``.beta`` is the in-kernel β telemetry — per-node net occupancy
    Σ_{e→i} w_e·β_e in *frames*, shape (B, R, N) / (R, N) matching
    ``freq_ppm`` — or None when the run did not ``record_beta``.  Unlike
    the ppm-scaled frequency records, β records are the raw float32
    kernel values, so ``.beta[..., -1, :]`` (see :meth:`beta_final`) IS
    the exact final occupancy: a chained (split) run with β recording
    reproduces the unsplit run's β stream bit-for-bit.

    ``.watermarks`` is the O(N) in-kernel excursion summary
    (:class:`repro.telemetry.Watermarks`: per-node max |β|, its record
    index, ν min/max in ppm) when the run did ``record_watermarks`` —
    available with or without a full ``.beta`` record, which is what
    lets 1M-node sparse runs report peak excursions at all.
    """

    engine: str
    tile_j: int
    nu: Optional[np.ndarray]
    beta: Optional[np.ndarray]
    watermarks: Optional[Watermarks]

    def __new__(cls, freq_ppm, psi, engine: str, tile_j: int, nu=None,
                beta=None, watermarks=None):
        self = tuple.__new__(cls, (freq_ppm, psi))
        self.engine = engine
        self.tile_j = int(tile_j)
        self.nu = nu
        self.beta = beta
        self.watermarks = watermarks
        return self

    @property
    def beta_final(self) -> Optional[np.ndarray]:
        """Exact per-node net occupancy at the last record (frames).

        Mirrors ``.nu``: the last β record is emitted unscaled by the
        kernel, so no rounding separates a chained run from an unsplit
        one.  None when the run did not record β.
        """
        return None if self.beta is None else self.beta[..., -1, :]


def latency_classes(lat_frames: np.ndarray,
                    quantum_frames: Optional[float] = None,
                    lat_classes: Optional[np.ndarray] = None,
                    warn: bool = True):
    """Group per-edge latencies (frames) into dense kernel classes.

    Returns (classes (C,) float32, inv (E,) int64 edge→class map).

    With ``lat_classes`` given, edges are assigned to the nearest of the
    provided class values, which must match to <= 1e-6 frames — this is
    how the scenario compiler keeps the class *axis* (and therefore the
    compiled kernel shapes) identical across piecewise-constant segments
    whose latency *values* differ.
    """
    lat_frames = np.asarray(lat_frames, np.float64)
    if lat_classes is not None:
        classes = np.asarray(lat_classes, np.float64).reshape(-1)
        inv = np.abs(lat_frames[:, None] - classes[None, :]).argmin(axis=1)
        # Relative tolerance: class vectors round-trip through float32
        # (the kernels' latency dtype), which costs ~1e-7 relative.
        err = np.abs(lat_frames - classes[inv])
        tol = 1e-6 + 1e-6 * np.abs(classes[inv])
        if np.any(err > tol):
            worst = int(err.argmax())
            raise ValueError(
                f"edge latency {lat_frames[worst]:.6f} frames does "
                f"not match any provided latency class (off by "
                f"{err[worst]:.3g}); classes={classes}")
        return classes.astype(np.float32), inv.astype(np.int64)
    if quantum_frames is None:
        classes, inv = np.unique(lat_frames, return_inverse=True)
        if len(classes) <= MAX_EXACT_CLASSES:
            return classes.astype(np.float32), inv.astype(np.int64)
        # Heterogeneous latencies (e.g. per-edge jittered cable lengths)
        # would make C explode and the (C, N, N) stack unaffordable;
        # merge with a quantum sized from the latency spread so the
        # class count stays bounded whatever the distribution.  rint
        # over a spread of S quanta can land in S+1 distinct bins, so
        # divide by MAX-1 to keep the bound at MAX exactly.
        spread = float(lat_frames.max() - lat_frames.min())
        quantum_frames = max(0.25, spread / (MAX_EXACT_CLASSES - 1))
        if warn:
            warnings.warn(
                f"densify: {len(classes)} exact latency classes > "
                f"{MAX_EXACT_CLASSES}; merging with quantum_frames="
                f"{quantum_frames:.3g} (pass quantum_frames explicitly to "
                "control this)", stacklevel=3)
    q = np.rint(lat_frames / quantum_frames).astype(np.int64)
    classes, inv = np.unique(q, return_inverse=True)
    return ((classes * quantum_frames).astype(np.float32),
            inv.astype(np.int64))


def densify(topo: Topology, links: LinkParams, omega_nom: float = OMEGA_NOM,
            quantum_frames: Optional[float] = None, tile: int = TILE,
            lat_classes: Optional[np.ndarray] = None,
            edge_w: Optional[np.ndarray] = None):
    """Edge list -> (A, lam_eff, lat_classes, n_padded).

    Edges are grouped into latency classes; the paper's setups have
    C ∈ {1, 2} (uniform short links, plus one long-fiber class in §5.6).
    With ``quantum_frames=None`` (default) each distinct physical latency
    becomes its own class, which keeps the dense path bit-consistent with
    the segment-sum simulator; pass a quantum (e.g. 0.25 frames) to merge
    near-equal latencies when a heterogeneous harness would otherwise
    produce too many classes.

    ``lat_classes`` pins the class axis to a precomputed latency vector
    (the scenario compiler's global class set, so every segment compiles
    to the same (C, N, N) shapes); ``edge_w`` scales each edge's
    adjacency/λeff contribution — weight 0 removes a dropped link from
    the aggregation entirely.

    The per-class scatter is a vectorized ``np.add.at`` (duplicate edges
    accumulate, so multigraphs are supported).
    """
    lat_frames = np.asarray(links.latency_s, np.float64) * omega_nom
    if lat_frames.ndim != 1:
        raise ValueError(
            "densify takes a single link set; per-draw (B, E) links are "
            "handled by simulate_ensemble_dense")
    classes, inv = latency_classes(lat_frames, quantum_frames, lat_classes)
    c = len(classes)
    n = topo.num_nodes
    n_pad = ((n + tile - 1) // tile) * tile
    a = np.zeros((c, n_pad, n_pad), np.float32)
    lam = np.zeros((c, n_pad, n_pad), np.float32)
    dst = np.asarray(topo.dst, np.int64)
    src = np.asarray(topo.src, np.int64)
    w = (np.ones(topo.num_edges, np.float64) if edge_w is None
         else np.asarray(edge_w, np.float64))
    np.add.at(a, (inv, dst, src), w)
    np.add.at(lam, (inv, dst, src), np.asarray(links.beta0, np.float64) * w)
    return (jnp.asarray(a), jnp.asarray(lam), jnp.asarray(classes), n_pad)


@functools.partial(jax.jit, static_argnames=("kp", "beta_off", "dt_frames",
                                             "use_ref"))
def bittide_step(psi, nu, nu_u, a, lam_eff, lat, kp, beta_off, dt_frames,
                 use_ref: bool = False, ctrl_mask=None):
    """One control period (per-step baseline path).

    Args:
      psi, nu, nu_u: (N_pad,) float32 state — ψ in frames, ν/ν_u as
        relative frequency offsets (dimensionless; ppm·1e-6).
      a, lam_eff: (C, N_pad, N_pad) float32 adjacency / λeff stacks from
        :func:`densify` (λeff in frames).
      lat: (C,) float32 per-class physical latencies in frames.
      kp, beta_off, dt_frames: **static** jit keys on this legacy path
        (rel-freq per frame, frames, frames per control period) — the
        fused engines trace the gains instead.
      ctrl_mask: optional (N_pad,) traced controller-enable mask.

    Returns (psi', nu'), both (N_pad,) float32.
    """
    if use_ref:
        psi2, nu2, _ = bittide_dense_step_ref(psi, nu, nu_u, a, lam_eff, lat,
                                              kp, beta_off, dt_frames,
                                              ctrl_mask)
        return psi2, nu2
    return bittide_step_pallas(psi, nu, nu_u, a, lam_eff, lat,
                               kp, beta_off, dt_frames, ctrl_mask=ctrl_mask,
                               interpret=_auto_interpret())


@functools.partial(jax.jit, static_argnames=("dt_frames", "num_records",
                                             "record_every", "engine",
                                             "tile_j", "interpret",
                                             "use_ref", "record_beta",
                                             "record_watermarks",
                                             "record_guard", "pulses"))
def _fused_engine(psi, nu, nu_u, kp, beta_off, ctrl_mask, a, lam_eff,
                  lamsum, lat, dt_frames, num_records, record_every, engine,
                  tile_j, interpret, use_ref, record_beta: bool = False,
                  record_watermarks: bool = False,
                  record_guard: bool = False, guard_lo=None, guard_hi=None,
                  guard_stop=None, pulses=None, c_est=None, edges=None,
                  lam_edges=None):
    """jit entry for the fused engines; one compile per (B, N, C, statics).

    Traced arguments (data, never compile keys — the scenario runner swaps
    them per segment against ONE compiled kernel):
      psi, nu, nu_u: (B_pad, N_pad) float32 state (ψ frames, ν relative).
      kp, beta_off: (B_pad,) per-draw controller gains (gain sweeps share
        one executable).
      ctrl_mask: (N_pad,) shared or (B_pad, N_pad) per-draw controller
        enables (0 = clock holdover).
      a, lam_eff: (C, N_pad, N_pad) adjacency / λeff stacks (frames).
      lamsum: (B_pad, N_pad) per-node λeff fold Σ_{e→i} w_e·λeff_e.
      lat: (B_pad, C) per-draw class latencies in frames.

    Static compile keys: ``dt_frames`` (frames per control period),
    ``num_records`` / ``record_every`` (telemetry grid), ``engine`` /
    ``tile_j`` (from :func:`repro.kernels.bittide_step.select_engine`),
    ``interpret``, ``use_ref``, ``record_beta``, ``record_watermarks``
    and ``record_guard`` — the telemetry switches are kernel *variants*
    (extra outputs + extra work), so ν-only runs keep their exact
    previous executable.

    With ``record_guard`` the traced ``guard_lo`` / ``guard_hi`` (per-draw
    band, frames per unit weighted degree) and ``guard_stop`` (last record
    to execute) feed the in-kernel reframing guard — the kernel freezes
    all records past the earliest trip and reports it in
    ``EngineOutputs.guard_state`` (sentinel ``num_records``); since the
    stop cap is traced too, a partial chunk reuses this exact executable.

    Which lane runs which controller and readout: both lanes run the
    proportional controller with continuous readout.  The FINC/FDEC
    controller (static ``pulses``, a ``repro.kernels.period.Pulses``,
    with its traced (B_pad, N_pad) ``c_est``) and integer readout (traced
    ``edges``, a ``repro.kernels.bittide_step.Incidence``, with the
    per-edge λeff ``lam_edges``, (1 | B_pad, E_pad)) run on the fused
    lane alone; the tiled lane and the oracle refuse them.

    Returns :class:`repro.kernels.EngineOutputs` with watermarks =
    (beta_abs_max, peak_record, nu_min, nu_max).
    """
    law = dict(pulses=pulses, c_est=c_est, edges=edges, lam_edges=lam_edges)
    if (use_ref or engine == "tiled") and (pulses is not None
                                           or edges is not None):
        raise ValueError("FINC/FDEC and integer readout run on the fused "
                         "lane alone")
    if use_ref:
        if record_guard:
            raise ValueError("record_guard is not supported on the "
                             "use_ref oracle lane")
        psi_f, nu_f, rec, brec = bittide_dense_multistep_ref(
            psi, nu, nu_u, a, lam_eff, lat, kp, beta_off, dt_frames,
            num_records, record_every, ctrl_mask,
            record_beta=record_beta or record_watermarks)
        wm = None
        if record_watermarks:
            # The oracle has no scratch to carry aggregates in; reduce its
            # full record inside the same jit (identical values, so the
            # in-kernel parity contract holds on this lane too).
            babs = jnp.abs(brec)
            wm = (jnp.max(babs, axis=0),
                  jnp.argmax(babs, axis=0).astype(jnp.int32),
                  jnp.min(rec, axis=0), jnp.max(rec, axis=0))
            if not record_beta:
                brec = None
        return EngineOutputs(psi=psi_f, nu=nu_f, freq=rec, beta=brec,
                             watermarks=wm)
    # Step-invariant per-node degree fold, hoisted out of the record grid.
    deg = a.sum(axis=(0, 2))
    guard_kw = dict(record_guard=record_guard, guard_lo=guard_lo,
                    guard_hi=guard_hi, guard_stop=guard_stop)
    if engine == "tiled":
        return bittide_tiled_fused_pallas(
            psi, nu, nu_u, a, deg, lamsum, lat, kp, beta_off, dt_frames,
            num_records=num_records, record_every=record_every,
            tile_j=tile_j, ctrl_mask=ctrl_mask, record_beta=record_beta,
            record_watermarks=record_watermarks, interpret=interpret,
            **guard_kw)
    return bittide_fused_pallas(
        psi, nu, nu_u, a, deg, lamsum, lat, kp, beta_off, dt_frames,
        num_records=num_records, record_every=record_every,
        ctrl_mask=ctrl_mask, record_beta=record_beta,
        record_watermarks=record_watermarks, interpret=interpret,
        **guard_kw, **law)


@functools.partial(jax.jit, static_argnames=("dt_frames", "num_records",
                                             "record_every", "tile_i",
                                             "interpret", "record_beta",
                                             "record_watermarks",
                                             "record_guard"))
def _sparse_engine(psi, nu, nu_u, kp, beta_off, ctrl_mask, nbr, latf, w,
                   lamsum, dt_frames, num_records, record_every, tile_i,
                   interpret, record_beta: bool = False,
                   record_watermarks: bool = False,
                   record_guard: bool = False, guard_lo=None, guard_hi=None,
                   guard_stop=None):
    """jit entry for the sparse ELL engine; one compile per (B, N, K, statics).

    Traced arguments (data, never compile keys — scenario segments AND
    chaos draws swap them against ONE compiled kernel):
      psi, nu, nu_u: (B_pad, N_pad) float32 state.
      kp, beta_off: (B_pad,) per-draw controller gains.
      ctrl_mask: (N_pad,) shared or (B_pad, N_pad) per-draw enables.
      nbr: (K, N_pad) int32 slot-major neighbor table.
      latf, w: (1 | B_pad, K, N_pad) slot latency (frames) / weight
        tables — per-draw rows carry per-draw LinkDrop victims and
        heterogeneous cable draws, which the dense lanes cannot trace.
      lamsum: (B_pad, N_pad) per-node λeff fold.

    Static compile keys: ``dt_frames``, ``num_records`` /
    ``record_every``, ``tile_i`` (node-panel width), ``interpret``,
    ``record_beta``, ``record_watermarks``, ``record_guard`` (the traced
    guard band / stop cap follow :func:`_fused_engine`'s contract).

    Returns :class:`repro.kernels.EngineOutputs`.
    """
    return bittide_sparse_pallas(
        psi, nu, nu_u, nbr, latf, w, lamsum, kp, beta_off, dt_frames,
        num_records=num_records, record_every=record_every, tile_i=tile_i,
        ctrl_mask=ctrl_mask, record_beta=record_beta,
        record_watermarks=record_watermarks, interpret=interpret,
        record_guard=record_guard, guard_lo=guard_lo, guard_hi=guard_hi,
        guard_stop=guard_stop)


@functools.partial(jax.jit, static_argnames=("kp", "beta_off", "dt_frames",
                                             "num_records", "record_every",
                                             "interpret", "use_ref",
                                             "record_beta",
                                             "record_watermarks",
                                             "record_guard"))
def _perstep_engine(psi, nu, nu_u, ctrl_mask, a, lam_eff, lat, kp, beta_off,
                    dt_frames, num_records, record_every, interpret,
                    use_ref, record_beta: bool = False,
                    record_watermarks: bool = False,
                    record_guard: bool = False, guard_lo=None, guard_hi=None,
                    guard_stop=None):
    """Capability-fallback engine with the fused engines' record contract.

    A scan of per-period 2-D kernels (one ``pallas_call`` per control
    period) that decimates ν telemetry to every ``record_every`` periods
    and accepts arbitrary initial state — so the scenario runner can chain
    it across segments exactly like the fused engines.  Gains are static
    compile keys on this path (it exists for capability, not speed), but
    the link arrays and the controller mask are traced, so a multi-segment
    scenario still compiles it exactly once.

    Shapes: single-draw (N_pad,) state, (C, N_pad, N_pad) stacks, (C,)
    class latencies in frames.  With ``record_beta`` each record issues
    ONE extra measurement launch of the 2-D kernel (``emit_beta=True``) on
    the post-update state — β stays an in-kernel quantity on this lane too
    — at (record_every+1)/record_every launch overhead.  With
    ``record_watermarks`` the running aggregates live in the scan carry,
    fed by the same in-kernel β measurements.

    With ``record_guard`` the trip record index rides the scan carry
    (sentinel ``num_records``): each record's β measurement is checked
    against the traced degree-scaled band and, once tripped (or past the
    traced ``guard_stop`` cap), every later record becomes a
    ``lax.cond`` no-op that carries the frozen state through — the same
    early-exit contract as the Pallas lanes, at scan granularity.

    Returns :class:`repro.kernels.EngineOutputs` (``guard_state`` is a
    scalar int32 on this single-draw lane).
    """

    def period(carry, _):
        psi, nu = carry
        if use_ref:
            psi, nu, _ = bittide_dense_step_ref(
                psi, nu, nu_u, a, lam_eff, lat, kp, beta_off, dt_frames,
                ctrl_mask)
        else:
            psi, nu = bittide_step_pallas(
                psi, nu, nu_u, a, lam_eff, lat, kp, beta_off, dt_frames,
                ctrl_mask=ctrl_mask, interpret=interpret)
        return (psi, nu), None

    def measure(psi, nu):
        # β is exactly invariant under a uniform ψ shift; center on the
        # host side of the kernel so its float32 partial sums stay small
        # (the fused engines center identically, in-kernel).
        psi_c = psi - jnp.mean(psi)
        if use_ref:
            return node_occupancy_ref(psi_c, nu, a, lam_eff, lat)
        return bittide_step_pallas(
            psi_c, nu, nu_u, a, lam_eff, lat, kp, beta_off, dt_frames,
            ctrl_mask=ctrl_mask, emit_beta=True, interpret=interpret)[2]

    measure_pass = record_beta or record_watermarks or record_guard
    if record_guard:
        deg = a.sum(axis=(0, 2))

    def step_record(state, wm, trip, t_idx):
        state, _ = jax.lax.scan(period, state, None, length=record_every)
        psi_t, nu_t = state
        bnode = measure(psi_t, nu_t) if measure_pass else None
        if record_watermarks:
            # Running aggregates in the scan carry (seeded at ∓inf), from
            # the SAME in-kernel β measurement the record lane emits.
            wm = fold_watermarks(wm, jnp.abs(bnode), nu_t, t_idx)
        if record_guard:
            viol = jnp.any(out_of_band(bnode, guard_lo, guard_hi, deg))
            trip = jnp.where(viol, t_idx, trip)
        return (state, wm, trip) + ((bnode,) if record_beta else ())

    def record(carry, t_idx):
        state, wm, trip = carry
        if record_guard:
            live_t = live(trip, t_idx, guard_stop)

            def frozen():
                # Early-exit no-op: carry the frozen state through (the
                # ν record re-emits the trip record's value; frozen β
                # slots are zeros — the host truncates at the trip).
                out = (state, wm, trip)
                if record_beta:
                    out = out + (jnp.zeros_like(state[0]),)
                return out

            res = jax.lax.cond(
                live_t, lambda: step_record(state, wm, trip, t_idx), frozen)
        else:
            res = step_record(state, wm, trip, t_idx)
        if record_beta:
            state, wm, trip, bnode = res
            out = (state[1], bnode)
        else:
            state, wm, trip = res
            out = state[1]
        return (state, wm, trip), out

    n_p = psi.shape[-1]
    wm0 = ((jnp.full((n_p,), -jnp.inf, jnp.float32),
            jnp.zeros((n_p,), jnp.int32),
            jnp.full((n_p,), jnp.inf, jnp.float32),
            jnp.full((n_p,), -jnp.inf, jnp.float32))
           if record_watermarks else ())
    trip0 = (never_tripped((), num_records) if record_guard
             else jnp.int32(0))
    ((psi, nu), wm, trip), rec = jax.lax.scan(
        record, ((psi, nu), wm0, trip0),
        jnp.arange(num_records, dtype=jnp.int32))
    wm = wm if record_watermarks else None
    trip = trip if record_guard else None
    if record_beta:
        return EngineOutputs(psi=psi, nu=nu, freq=rec[0], beta=rec[1],
                             watermarks=wm, guard_state=trip)
    return EngineOutputs(psi=psi, nu=nu, freq=rec, beta=None,
                         watermarks=wm, guard_state=trip)


def _pad_batch(ppm_u: np.ndarray, n: int, n_pad: int) -> Tuple[jnp.ndarray, int]:
    """(B, n) ppm draws -> (B_pad, n_pad) ν_u with inert padding."""
    b = ppm_u.shape[0]
    b_pad = ((b + SUBLANE - 1) // SUBLANE) * SUBLANE
    nu_u = np.zeros((b_pad, n_pad), np.float32)
    nu_u[:b, :n] = ppm_u * 1e-6
    return jnp.asarray(nu_u), b_pad


def _pad_gain(gain: np.ndarray, b_pad: int) -> jnp.ndarray:
    """(B,) per-draw gains -> (B_pad,) (padding rows are independent)."""
    out = np.zeros((b_pad,), np.float32)
    out[:gain.shape[0]] = gain
    return jnp.asarray(out)


def _pad_state(state: np.ndarray, b_pad: int, n_pad: int) -> jnp.ndarray:
    """(B, N) chained state -> (B_pad, N_pad) with inert zero padding."""
    b, n = np.asarray(state).shape
    out = np.zeros((b_pad, n_pad), np.float32)
    out[:b, :n] = np.asarray(state, np.float32)
    return jnp.asarray(out)


def _resolve_init(init, b: int, n: int, b_pad: int, n_pad: int, nu_u):
    """Seed (psi0, nu0) from ``init`` (a prior result or a (ψ, ν) pair)."""
    if init is None:
        return jnp.zeros_like(nu_u), nu_u
    init_psi = init[1] if isinstance(init, DenseResult) else init[0]
    init_nu = init.nu if isinstance(init, DenseResult) else init[1]
    if init_nu is None:
        raise ValueError("init DenseResult lacks .nu (produced by a "
                         "pre-chaining build?)")
    init_psi = np.atleast_2d(init_psi)
    init_nu = np.atleast_2d(init_nu)
    for name, arr in (("psi", init_psi), ("nu", init_nu)):
        if arr.shape != (b, n):
            raise ValueError(
                f"init {name} must be (B, N) = ({b}, {n}), got "
                f"{arr.shape}")
    return _pad_state(init_psi, b_pad, n_pad), _pad_state(init_nu, b_pad,
                                                          n_pad)


def _resolve_mask(ctrl_mask, b: int, n: int, b_pad: int, n_pad: int):
    """Pad the controller-enable mask — (N,) shared or (B, N) per-draw —
    to kernel layout (padding nodes/draws stay enabled; inert anyway)."""
    mask_np = (None if ctrl_mask is None
               else np.asarray(ctrl_mask, np.float32))
    if mask_np is not None and mask_np.ndim == 2:
        if mask_np.shape != (b, n):
            raise ValueError(f"per-draw ctrl_mask must be ({b}, {n}), got "
                             f"{mask_np.shape}")
        mask_pad = np.ones((b_pad, n_pad), np.float32)
        mask_pad[:b, :n] = mask_np
    else:
        mask_pad = np.ones((n_pad,), np.float32)
        if mask_np is not None:
            mask_pad[:n] = mask_np
    return mask_pad


def _link_rows(links: LinkParams, b: int, num_edges: int):
    """Normalize LinkParams to per-draw (B, E) latency/beta0 rows.

    Returns (batched, lat_s (B, E) float64, beta0 (B, E) float64,
    beta0_batched) — ``batched`` is True when either field carried a
    per-draw leading axis (the Monte-Carlo cable-length-distribution
    regime).
    """
    lat = np.asarray(links.latency_s, np.float64)
    b0 = np.asarray(links.beta0, np.float64)
    batched = lat.ndim == 2 or b0.ndim == 2
    for name, arr in (("latency_s", lat), ("beta0", b0)):
        if arr.ndim == 2 and arr.shape != (b, num_edges):
            raise ValueError(
                f"per-draw links.{name} must be (B, E) = ({b}, "
                f"{num_edges}), got {arr.shape}")
        if arr.ndim == 1 and arr.shape != (num_edges,):
            raise ValueError(
                f"links.{name} must be ({num_edges},) or ({b}, "
                f"{num_edges}), got {arr.shape}")
    beta0_batched = b0.ndim == 2
    lat = np.broadcast_to(lat, (b, num_edges)) if lat.ndim == 1 else lat
    b0 = np.broadcast_to(b0, (b, num_edges)) if b0.ndim == 1 else b0
    return batched, lat, b0, beta0_batched


def _per_draw_class_values(lat_frames: np.ndarray, classes: np.ndarray,
                           inv: np.ndarray) -> np.ndarray:
    """(B, E) per-draw edge latencies -> (B, C) per-draw class values.

    The dense engines batch link parameters along the class axis, so all
    edges of one class must share one latency *within each draw* (the
    class structure — which edge belongs to which class — is shared
    across draws).  Fully heterogeneous per-draw links belong on the
    segment-sum lane (``repro.core.simulate_ensemble``).
    """
    c = len(classes)
    rep = np.array([int(np.argmax(inv == ci)) for ci in range(c)])
    latv = lat_frames[:, rep]                                 # (B, C)
    dev = np.abs(lat_frames - latv[:, inv])
    err = (dev / (1.0 + np.abs(latv[:, inv]))).max(initial=0.0)
    if err > 1e-6:
        raise ValueError(
            "per-draw link latencies must share the class structure (one "
            "latency per class per draw; edges of a class may not differ "
            f"within a draw — max deviation {err:.3g} frames).  Use "
            "repro.core.simulate_ensemble (segment-sum lane) for fully "
            "heterogeneous per-draw links.")
    return latv.astype(np.float32)


def _fold_index(dst, b_rows: int, n_pad: int) -> np.ndarray:
    """Flat (b_rows·E,) slots ``row·n_pad + dst`` of a (b_rows, E) per-edge
    array in (b_rows, n_pad) per-node rows, row-major."""
    return (np.arange(b_rows, dtype=np.int64)[:, None] * n_pad
            + np.asarray(dst, np.int64)[None, :]).ravel()


def _lamsum_host(topo: Topology, beta0: np.ndarray, edge_w, b_rows: int,
                 n_pad: int, index: Optional[np.ndarray] = None) -> np.ndarray:
    """Per-node λeff fold Σ_{e→i} w_e·β0_e as (b_rows, n_pad) rows.

    One ``np.bincount`` over :func:`_fold_index` (``index``, when the
    caller keeps it): each node adds its edges' terms in edge order in
    float64, the order and precision of ``np.add.at``, so the fold is
    the same to the bit."""
    w = (np.ones(topo.num_edges, np.float64) if edge_w is None
         else np.asarray(edge_w, np.float64))
    contrib = np.broadcast_to(beta0 * w, (b_rows, topo.num_edges))
    if index is None:
        index = _fold_index(topo.dst, b_rows, n_pad)
    out = np.bincount(index, weights=contrib.ravel(),
                      minlength=b_rows * n_pad)
    return out.reshape(b_rows, n_pad).astype(np.float32)


def _host_watermarks(wm_dev, num_records: int, b: Optional[int],
                     n: int) -> Watermarks:
    """Device watermark tuple -> host :class:`Watermarks`.

    Slices away kernel padding ((b, n) rows for batched lanes, (n,) for
    the per-step single-draw lane when ``b`` is None) and converts the
    ν extremes to ppm, matching ``freq_ppm``'s units."""
    bmax, idx, lo, hi = wm_dev

    def cut(x):
        x = np.asarray(x)
        return x[:b, :n] if b is not None else x[:n]

    return Watermarks(beta_abs_max=cut(bmax), peak_record=cut(idx),
                      nu_min_ppm=cut(lo) * 1e6, nu_max_ppm=cut(hi) * 1e6,
                      num_records=num_records)


def _pad_table_rows(tbl, b_pad: int):
    """Pad a per-draw (B, K, N) ELL table to (B_pad, K, N) by repeating
    draw 0 (padding draws are dead rows; shared (1, K, N) passes through)."""
    if tbl.shape[0] in (1, b_pad):
        return tbl
    pad = jnp.broadcast_to(tbl[:1],
                           (b_pad - tbl.shape[0],) + tbl.shape[1:])
    return jnp.concatenate([tbl, pad], axis=0)


def _run_sparse(topo: Topology, lat_be, beta0_be, beta0_batched: bool,
                batched: bool, edge_w_np, ppm_u, b: int, n: int, kp,
                beta_off, dt: float, omega_nom: float, num_records: int,
                record_every: int, tile_j, init, ctrl_mask,
                record_beta: bool, record_watermarks: bool,
                interp: bool) -> DenseResult:
    """The sparse ELL lane of :func:`simulate_ensemble_dense`.

    No densify, no latency classes: the slot tables carry every edge's
    own latency (frames) directly, so fully heterogeneous per-draw links
    AND per-draw edge weights (LinkDrop victims) are traced data here —
    the regimes the dense lanes must reject.
    """
    per_draw_w = edge_w_np is not None and edge_w_np.ndim == 2
    n_pad = ((n + TILE - 1) // TILE) * TILE
    lat_tab = (lat_be if batched else lat_be[0]) * omega_nom
    nbr, latf, w = ellify(topo, lat_tab, edge_w=edge_w_np, n_pad=n_pad)
    rows_l = b if (beta0_batched or per_draw_w) else 1
    beta0_arg = beta0_be if beta0_batched else beta0_be[0][None]
    lamsum_rows = _lamsum_host(topo, beta0_arg, edge_w_np, rows_l, n_pad)
    nu_u, b_pad = _pad_batch(ppm_u, n, n_pad)
    psi0, nu0 = _resolve_init(init, b, n, b_pad, n_pad, nu_u)
    mask_pad = _resolve_mask(ctrl_mask, b, n, b_pad, n_pad)
    lamsum_pad = np.zeros((b_pad, n_pad), np.float32)
    lamsum_pad[:b] = np.broadcast_to(lamsum_rows, (b, n_pad))
    latf = _pad_table_rows(latf, b_pad)
    w = _pad_table_rows(w, b_pad)
    k = nbr.shape[0]
    rows_t = max(latf.shape[0], w.shape[0])
    ti = (int(tile_j) if tile_j is not None
          else sparse_panel(b_pad, n_pad, k, rows_t, record_beta=record_beta,
                            record_watermarks=record_watermarks) or TILE)

    out = _sparse_engine(
        psi0, nu0, nu_u, _pad_gain(kp, b_pad), _pad_gain(beta_off, b_pad),
        jnp.asarray(mask_pad), nbr, latf, w, jnp.asarray(lamsum_pad),
        float(omega_nom * dt), int(num_records), int(record_every),
        int(ti), interp, bool(record_beta), bool(record_watermarks))

    freq = np.asarray(out.freq)[:, :b, :n] * 1e6   # (R, B, N)
    beta = (np.ascontiguousarray(
        np.transpose(np.asarray(out.beta)[:, :b, :n], (1, 0, 2)))
        if record_beta else None)
    return DenseResult(
        np.ascontiguousarray(np.transpose(freq, (1, 0, 2))),
        np.asarray(out.psi)[:b, :n], "sparse", ti,
        nu=np.asarray(out.nu)[:b, :n], beta=beta,
        watermarks=(_host_watermarks(out.watermarks, num_records, b, n)
                    if record_watermarks else None))


def simulate_ensemble_dense(topo: Topology, links: LinkParams, ppm_u,
                            steps: int, kp, dt: float = 1e-3,
                            beta_off=0.0, record_every: int = 1,
                            omega_nom: float = OMEGA_NOM,
                            use_ref: bool = False,
                            engine: Optional[str] = None,
                            tile_j: Optional[int] = None,
                            init=None, ctrl_mask=None,
                            lat_classes: Optional[np.ndarray] = None,
                            edge_w: Optional[np.ndarray] = None,
                            record_beta: Optional[bool] = None,
                            record_watermarks: Optional[bool] = None,
                            options=None, telemetry=None) -> DenseResult:
    """Batched fused synchronization: B draws in one compiled call.

    Args:
      links: per-edge physical parameters.  ``latency_s`` / ``beta0`` may
        carry a per-draw leading axis — (B, E) — to run a cable-length
        distribution (one link sample per draw).  The dense lane requires
        per-draw latencies to share the latency-class structure (one value
        per class per draw); fully heterogeneous per-draw links belong on
        the segment-sum lane.
      ppm_u: (B, N) unadjusted oscillator offsets in ppm, one row per
        independent draw (the paper's ±8 ppm Monte Carlo sweeps).
      steps: control periods to advance (floor-truncated to a multiple of
        ``record_every``).
      kp, beta_off: controller gains — scalars, or length-B arrays with
        one value per draw (the batched Fig-15 gain-sweep axis).  Gains
        are traced through the kernels, so sweeping them never recompiles.
      record_every: in-kernel telemetry decimation.
      use_ref: run the jnp multistep oracle instead of the Pallas kernel.
      engine: "auto" (tile-size heuristic via ``select_engine``), or force
        "fused" (VMEM-resident adjacency), "tiled" (HBM-streamed j
        panels), "sparse" (edge-major ELL gather for bounded-degree
        mega-scale graphs — also the only compiled lane accepting
        per-draw (B, E) ``edge_w`` and fully heterogeneous per-draw
        latencies), or "per-step" (scan-of-kernels fallback).
      tile_j: j-panel width for the tiled engine (defaults to the
        heuristic's choice; must be a multiple of TILE dividing padded N).
      init: optional ``(psi, nu)`` pair of (B, N) arrays (or a prior
        ``DenseResult`` with ``.nu``) seeding the state — the scenario
        runner's segment-chaining hook.  Default: cold start (ψ = 0,
        ν = ν_u).
      ctrl_mask: optional (N,) shared or (B, N) per-draw controller-enable
        mask; masked-out nodes hold their previous ν (clock holdover).
        Traced — toggling it never recompiles (per-draw chaos campaigns
        give each draw its own holdover victims).
      lat_classes: optional precomputed latency-class vector (frames)
        pinning the dense class axis (scenario segments share one global
        class set so every segment hits one compiled kernel).
      edge_w: optional (E,) edge weights; weight 0 removes a (dropped)
        link from the error aggregation.  A (B, E) per-draw matrix (chaos
        campaigns with per-draw LinkDrop victims) routes to the sparse
        lane, where weights live in traced slot tables.
      record_beta: also record the per-node net occupancy β_i =
        Σ_{e→i} w_e·β_e (frames) in-kernel at every record point — the
        paper's central measured quantity (bounded buffer excursions,
        Figs. 12–14, 17–19).  A compile-time kernel variant: the ν-only
        fast path is byte-identical when off.
      record_watermarks: carry O(B·N) excursion watermarks in-kernel —
        per-node max |β| with its record index plus ν min/max — so the
        run's peak excursion and frequency spread are available WITHOUT
        materializing any (R, B, N) record (how a large sparse run
        reports them).  Also a compile-time kernel
        variant, independent of (and composable with) ``record_beta``.
      options: :class:`repro.kernels.EngineOptions` — the typed home of
        ``engine``.  An explicit ``engine=`` wins over the field.
      telemetry: :class:`repro.telemetry.Telemetry` — the typed home of
        ``record_beta`` / ``record_watermarks`` (both legacy kwargs
        deprecated).  ``trace`` / ``guard`` need the scenario runner and
        raise here.

    Returns:
      DenseResult ``(freq_ppm (B, R, N), psi (B, N))`` with
      R = steps // record_every, ``.engine`` / ``.tile_j`` metadata,
      ``.nu`` — the exact final frequencies for chaining — ``.beta``
      ((B, R, N) frames, or None without ``record_beta``) and
      ``.watermarks`` (:class:`repro.telemetry.Watermarks` or None).
    """
    opts = resolve_options(options, "simulate_ensemble_dense",
                           engine=engine)
    tel = resolve_telemetry(telemetry, "simulate_ensemble_dense",
                            beta=record_beta, watermarks=record_watermarks)
    if tel.trace or tel.guard:
        raise ValueError(
            "simulate_ensemble_dense: Telemetry.trace / Telemetry.guard "
            "need the scenario runner — use run_scenario, which owns the "
            "flight recorder and the reframing splice")
    if opts.chunk_records is not None:
        raise ValueError(
            "simulate_ensemble_dense runs one launch per call; "
            "chunk_records is a run_scenario option")
    engine = opts.engine
    record_beta = tel.beta
    record_watermarks = tel.watermarks
    # The kernel variant that runs: dispatch budgets its telemetry buffers.
    telemetry = dict(record_beta=bool(record_beta),
                     record_watermarks=bool(record_watermarks))
    ppm_u = np.atleast_2d(np.asarray(ppm_u, np.float32))
    if ppm_u.shape[1] != topo.num_nodes:
        raise ValueError(
            f"ppm_u must be (B, {topo.num_nodes}), got {ppm_u.shape}")
    num_records = steps // record_every
    if num_records < 1:
        raise ValueError("steps must be >= record_every")
    b = ppm_u.shape[0]
    n = topo.num_nodes
    kp = broadcast_gain(kp, b, "kp")
    beta_off = broadcast_gain(beta_off, b, "beta_off")

    batched, lat_be, beta0_be, beta0_batched = _link_rows(
        links, b, topo.num_edges)
    interp = _auto_interpret()

    # --- sparse ELL lane -------------------------------------------------
    # Decided BEFORE densify: at the sparse regime's scale a (C, N, N)
    # stack must never be materialized, and per-draw edge
    # weights exist only as slot tables.
    edge_w_np = None if edge_w is None else np.asarray(edge_w, np.float64)
    per_draw_w = edge_w_np is not None and edge_w_np.ndim == 2
    if per_draw_w and edge_w_np.shape != (b, topo.num_edges):
        raise ValueError(
            f"per-draw edge_w must be (B, E) = ({b}, {topo.num_edges}), "
            f"got {edge_w_np.shape}")
    sparse = engine == "sparse"
    if engine == "auto" and not use_ref:
        # Probe the dispatch heuristic with the degree bound: bounded-
        # degree mega-scale topologies route to the sparse lane when no
        # dense working set fits (same class count the dense path would
        # compute, derived at edge-list cost).
        classes_probe, _ = latency_classes(
            lat_be[0] * omega_nom, lat_classes=lat_classes, warn=False)
        b_probe = ((b + SUBLANE - 1) // SUBLANE) * SUBLANE
        n_probe = ((n + TILE - 1) // TILE) * TILE
        sparse = select_engine(b_probe, n_probe, len(classes_probe),
                               max_deg=max_in_degree(topo),
                               **telemetry)[0] == "sparse"
    if per_draw_w and not sparse:
        raise ValueError(
            "per-draw (B, E) edge_w needs the sparse or segment-sum "
            "engine (the dense (C, N, N) adjacency stacks are shared "
            "across draws)")
    if sparse:
        if use_ref:
            raise ValueError("use_ref does not support the sparse engine "
                             "(validate against segment-sum instead)")
        return _run_sparse(
            topo, lat_be, beta0_be, beta0_batched, batched, edge_w_np,
            ppm_u, b, n, kp, beta_off, dt, omega_nom, num_records,
            record_every, tile_j, init, ctrl_mask, bool(record_beta),
            bool(record_watermarks), interp)
    # ---------------------------------------------------------------------

    if beta0_batched and use_ref:
        raise ValueError("use_ref does not support per-draw beta0 (the "
                         "oracle's lam_eff tensor is shared across draws)")
    if batched:
        # Class structure from draw 0 (possibly quantum-merged); snap the
        # densified grouping to it so the class AXIS is draw-invariant,
        # then read each draw's class VALUES off its own latency rows.
        lat_frames_be = lat_be * omega_nom
        classes_np, inv = latency_classes(lat_frames_be[0],
                                          lat_classes=lat_classes)
        classes_np = np.asarray(classes_np, np.float64)
        latv = _per_draw_class_values(lat_frames_be, classes_np, inv)
        links0 = LinkParams(latency_s=classes_np[inv] / omega_nom,
                            beta0=beta0_be[0])
    else:
        links0 = LinkParams(latency_s=lat_be[0], beta0=beta0_be[0])
    a, lam_eff, classes, n_pad = densify(
        topo, links0, omega_nom,
        lat_classes=classes_np if batched else lat_classes, edge_w=edge_w)
    c = a.shape[0]
    classes_np = np.asarray(classes, np.float64)
    if not batched:
        latv = np.broadcast_to(classes_np.astype(np.float32)[None, :],
                               (b, c))
    lamsum_rows = _lamsum_host(topo, beta0_be if beta0_batched
                               else beta0_be[0][None], edge_w,
                               b if beta0_batched else 1, n_pad)

    nu_u, b_pad = _pad_batch(ppm_u, n, n_pad)
    psi0, nu0 = _resolve_init(init, b, n, b_pad, n_pad, nu_u)
    mask_pad = _resolve_mask(ctrl_mask, b, n, b_pad, n_pad)

    if use_ref:
        chosen, tj = "ref", n_pad
    elif engine == "auto":
        # The tile-size heuristic replaces the old VMEM cliff; it applies
        # under interpret too so CPU validation exercises TPU dispatch.
        chosen, tj = select_engine(b_pad, n_pad, c, **telemetry)
    elif engine in ("fused", "tiled", "per-step"):
        chosen = engine
        tj = tile_j if tile_j is not None else (
            select_engine(b_pad, n_pad, c, **telemetry)[1]
            if engine == "tiled" else n_pad)
    else:
        raise ValueError(f"unknown engine {engine!r}")
    if chosen == "tiled" and tile_j is not None:
        tj = tile_j

    if chosen == "per-step":
        # Nothing fits VMEM (huge C·N): scan of per-period 2-D kernels,
        # decimating its per-period telemetry to the requested records.
        # Gains are static compile keys on this path — it exists for
        # capability, not speed.
        if engine == "auto":
            warnings.warn(
                f"no fused/tiled working set fits the VMEM budget for "
                f"B={b_pad}, N={n_pad}, C={c}; falling back to the per-step "
                "kernel", stacklevel=2)
        freqs, psis, nus, betas, wms = [], [], [], [], []
        mask_j = jnp.asarray(mask_pad)
        mask_row = (lambda bi: mask_j[bi]) if mask_j.ndim == 2 \
            else (lambda bi: mask_j)
        for bi in range(b):
            if beta0_batched:
                _, lam_bi, _, _ = densify(
                    topo, LinkParams(latency_s=lat_be[bi],
                                     beta0=beta0_be[bi]),
                    omega_nom, lat_classes=classes_np, edge_w=edge_w)
            else:
                lam_bi = lam_eff
            out = _perstep_engine(
                psi0[bi], nu0[bi], nu_u[bi], mask_row(bi), a, lam_bi,
                jnp.asarray(latv[bi]), float(kp[bi]), float(beta_off[bi]),
                float(omega_nom * dt), int(num_records), int(record_every),
                interp, bool(use_ref), bool(record_beta),
                bool(record_watermarks))
            freqs.append(np.asarray(out.freq)[:, :n] * 1e6)
            psis.append(np.asarray(out.psi)[:n])
            nus.append(np.asarray(out.nu)[:n])
            if record_beta:
                betas.append(np.asarray(out.beta)[:, :n])
            if record_watermarks:
                wms.append(_host_watermarks(out.watermarks, num_records,
                                            None, n))
        wm_res = Watermarks.stack(wms) if record_watermarks else None
        return DenseResult(np.stack(freqs), np.stack(psis), "per-step", 0,
                           nu=np.stack(nus),
                           beta=np.stack(betas) if record_beta else None,
                           watermarks=wm_res)

    lat_pad = np.zeros((b_pad, c), np.float32)
    lat_pad[:b] = latv
    lat_pad[b:] = classes_np.astype(np.float32)[None, :]
    lamsum_pad = np.zeros((b_pad, n_pad), np.float32)
    lamsum_pad[:b] = np.broadcast_to(lamsum_rows, (b, n_pad))

    out = _fused_engine(
        psi0, nu0, nu_u, _pad_gain(kp, b_pad), _pad_gain(beta_off, b_pad),
        jnp.asarray(mask_pad), a, lam_eff, jnp.asarray(lamsum_pad),
        jnp.asarray(lat_pad), float(omega_nom * dt), int(num_records),
        int(record_every), str(chosen), int(tj), interp, bool(use_ref),
        bool(record_beta), bool(record_watermarks))

    freq = np.asarray(out.freq)[:, :b, :n] * 1e6   # (R, B, N)
    beta = (np.ascontiguousarray(
        np.transpose(np.asarray(out.beta)[:, :b, :n], (1, 0, 2)))
        if record_beta else None)
    return DenseResult(
        np.ascontiguousarray(np.transpose(freq, (1, 0, 2))),
        np.asarray(out.psi)[:b, :n], chosen, tj,
        nu=np.asarray(out.nu)[:b, :n], beta=beta,
        watermarks=(_host_watermarks(out.watermarks, num_records, b, n)
                    if record_watermarks else None))


def simulate_fused(topo: Topology, links: LinkParams, ppm_u, steps: int,
                   kp: float, dt: float = 1e-3, beta_off: float = 0.0,
                   record_every: int = 1, omega_nom: float = OMEGA_NOM,
                   use_ref: bool = False, engine: Optional[str] = None,
                   tile_j: Optional[int] = None, init=None,
                   ctrl_mask=None, lat_classes=None,
                   edge_w=None, record_beta: Optional[bool] = None,
                   record_watermarks: Optional[bool] = None,
                   options=None, telemetry=None) -> DenseResult:
    """Single-draw fused run; returns (freq_ppm (R, N), psi (N,)).

    ``init`` takes (psi (N,), nu (N,)) for segment chaining; the scenario
    kwargs (``ctrl_mask``, ``lat_classes``, ``edge_w``) pass through to
    :func:`simulate_ensemble_dense`, as do ``options=`` (EngineOptions)
    and ``telemetry=`` (Telemetry; ``.beta`` is then (R, N) per-node net
    occupancy in frames, ``.watermarks`` per-node (N,) aggregates).  The
    legacy ``record_beta=`` / ``record_watermarks=`` kwargs are
    one-release deprecation shims resolved here (so the warning names
    this entry point, not the delegate).
    """
    opts = resolve_options(options, "simulate_fused", engine=engine)
    tel = resolve_telemetry(telemetry, "simulate_fused",
                            beta=record_beta, watermarks=record_watermarks)
    if init is not None and not isinstance(init, DenseResult):
        init = (np.atleast_2d(init[0]), np.atleast_2d(init[1]))
    res = simulate_ensemble_dense(
        topo, links, np.atleast_2d(np.asarray(ppm_u, np.float32)), steps, kp,
        dt=dt, beta_off=beta_off, record_every=record_every,
        omega_nom=omega_nom, use_ref=use_ref,
        tile_j=tile_j, init=init, ctrl_mask=ctrl_mask,
        lat_classes=lat_classes, edge_w=edge_w,
        options=opts, telemetry=tel)
    freq, psi = res
    return DenseResult(freq[0], psi[0], res.engine, res.tile_j,
                       nu=None if res.nu is None else res.nu[0],
                       beta=None if res.beta is None else res.beta[0],
                       watermarks=None if res.watermarks is None
                       else res.watermarks[0])


def simulate_dense(topo: Topology, links: LinkParams, ppm_u, steps: int,
                   kp: float, dt: float = 1e-3, beta_off: float = 0.0,
                   omega_nom: float = OMEGA_NOM,
                   use_ref: bool = False) -> DenseResult:
    """Fused-kernel synchronization run; returns (freq_ppm (T,N), psi (N,)).

    Back-compat API (per-period telemetry: T == steps, freq in ppm, ψ in
    frames); delegates to the fused multi-period engine with
    ``record_every=1``.
    """
    return simulate_fused(topo, links, ppm_u, steps, kp, dt=dt,
                          beta_off=beta_off, record_every=1,
                          omega_nom=omega_nom, use_ref=use_ref)


def simulate_dense_perstep(topo: Topology, links: LinkParams, ppm_u,
                           steps: int, kp: float, dt: float = 1e-3,
                           beta_off: float = 0.0,
                           omega_nom: float = OMEGA_NOM,
                           use_ref: bool = False) -> DenseResult:
    """The pre-fusion engine: one ``pallas_call`` per control period inside
    a ``lax.scan``.  Kept as the benchmark baseline — it re-streams the
    (C, N, N) adjacency and round-trips the (N,) state through HBM every
    period, which is exactly the overhead the fused engine removes."""
    a, lam_eff, lat, n_pad = densify(topo, links, omega_nom)
    nu_u = jnp.zeros((n_pad,), jnp.float32).at[:topo.num_nodes].set(
        jnp.asarray(np.asarray(ppm_u, np.float32) * 1e-6))
    psi = jnp.zeros((n_pad,), jnp.float32)
    nu = nu_u
    dt_frames = float(omega_nom * dt)

    step = functools.partial(bittide_step, kp=float(kp),
                             beta_off=float(beta_off), dt_frames=dt_frames,
                             use_ref=use_ref)

    def body(carry, _):
        psi, nu = carry
        psi, nu = step(psi, nu, nu_u, a, lam_eff, lat)
        return (psi, nu), nu * 1e6

    (psi, nu), freq = jax.lax.scan(body, (psi, nu), None, length=steps)
    return DenseResult(np.asarray(freq[:, :topo.num_nodes]),
                       np.asarray(psi[:topo.num_nodes]), "per-step", 0)
