"""The abstract frame model (paper §6), vectorized in JAX.

The paper's model:

    dθ_i/dt   = ω_i(t)
    β_{j→i}(t) = ⌊θ_j(t − l_{j→i})⌋ − ⌊θ_i(t)⌋ + λ_{j→i}
    ω updated piecewise-constantly at each controller period from eq. (1).

Absolute phases reach ~1.25e10 ticks within a 100 s experiment, far beyond
float32.  We therefore integrate *relative* coordinates, which is exact under
the model's piecewise-constant-ω semantics:

    ψ_i = θ_i − ω_nom·t            (|ψ| ≲ 1e6 ticks)
    ν_i = ω_i/ω_nom − 1            (|ν| ≲ 1e-4)

    β_{j→i} = ψ_j − ν_j·ω_nom·l_{j→i} − ψ_i + λeff_{j→i}
    λeff    = λ − ω_nom·l          (constant; fixed by initial occupancy)

The hardware's floor quantization is an O(1)-frame effect; ``quantize_beta``
rounds β to integers to model it (the analysis model in [10] omits floors).

The simulation advances at a fixed control period ``dt``; between control
events frequencies are constant, so phase integration is exact — this is the
same event semantics as the Callisto simulator, restricted to synchronous
sampling (the paper notes behavior is insensitive to sampling jitter and to
the actuation delay d).
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .controller import (ControllerConfig, controller_init, controller_step,
                         holdover_freeze)
from .topology import Topology

__all__ = ["LinkParams", "SimConfig", "SimResult", "EnsembleResult",
           "simulate", "simulate_ensemble", "make_links", "broadcast_gain",
           "OMEGA_NOM"]

OMEGA_NOM = 125e6  # frames/s — the paper's 125 MHz node clock.

# Calibrated physical constants (paper §5.6): group velocity in fiber such
# that a 2 km spool (~1 km per direction) adds ~1231 frames of round-trip
# logical latency, and 16 frames of transceiver pipeline per direction.
SIGNAL_VELOCITY = 2.03e8   # m/s
PIPE_FRAMES = 16.0         # serdes/transceiver pipeline, frames per direction
EB_INIT = 18.0             # elastic buffer init: 32-deep, half-full + 2 (§5.2)


@dataclasses.dataclass(frozen=True)
class LinkParams:
    """Per-directed-edge physical link parameters.

    latency_s: one-way physical latency (cable + transceiver pipeline).
    beta0: initial elastic-buffer occupancy in frames (normalized; the DDC
      phase uses 0 = half-full).

    Either field may carry a per-draw leading axis — shape (B, E) — for
    Monte Carlo over cable-length distributions; the batched simulation
    lanes (``simulate_ensemble`` / ``simulate_ensemble_dense``) consume
    one row per oscillator draw.  Single-run entry points require the
    plain (E,) form (use :meth:`draw`).
    """

    latency_s: np.ndarray
    beta0: np.ndarray

    @property
    def num_edges(self) -> int:
        return int(np.asarray(self.latency_s).shape[-1])

    @property
    def num_draws(self) -> Optional[int]:
        """Leading batch size if any field is per-draw, else None."""
        for arr in (self.latency_s, self.beta0):
            arr = np.asarray(arr)
            if arr.ndim == 2:
                return int(arr.shape[0])
        return None

    def draw(self, b: int) -> "LinkParams":
        """The (E,)-shaped link set of draw ``b``."""
        pick = lambda arr: (np.asarray(arr)[b] if np.asarray(arr).ndim == 2
                            else np.asarray(arr))
        return LinkParams(latency_s=pick(self.latency_s),
                          beta0=pick(self.beta0))


def make_links(
    topo: Topology,
    cable_m: float | np.ndarray = 2.0,
    beta0: float | np.ndarray = 0.0,
    omega_nom: float = OMEGA_NOM,
    pipe_frames: float = PIPE_FRAMES,
    velocity: float = SIGNAL_VELOCITY,
) -> LinkParams:
    """Build LinkParams from cable lengths in meters (per directed edge).

    ``cable_m`` / ``beta0`` accept scalars, (E,) per-edge arrays, or
    2-D per-draw arrays broadcastable to (B, E) — e.g. a (B, 1) column of
    per-draw scale factors or a full (B, E) cable-length sample — which
    yields batched LinkParams for the ensemble lanes.
    """
    cable = np.asarray(cable_m, np.float64)
    b0 = np.asarray(beta0, np.float64)
    if cable.ndim == 2 or b0.ndim == 2:
        b = cable.shape[0] if cable.ndim == 2 else b0.shape[0]
        if (cable.ndim == 2 and b0.ndim == 2
                and cable.shape[0] != b0.shape[0]):
            raise ValueError(
                f"per-draw cable_m and beta0 disagree on B: "
                f"{cable.shape[0]} vs {b0.shape[0]}")
        shape = (b, topo.num_edges)
    else:
        shape = (topo.num_edges,)
    cable = np.broadcast_to(cable, shape)
    lat = cable / velocity + pipe_frames / omega_nom
    b0 = np.broadcast_to(b0, shape)
    return LinkParams(latency_s=lat.astype(np.float64), beta0=b0.astype(np.float64))


@dataclasses.dataclass(frozen=True)
class SimConfig:
    omega_nom: float = OMEGA_NOM
    dt: float = 1e-3            # control period, seconds
    steps: int = 50_000
    record_every: int = 10      # telemetry decimation (keeps big sims small)
    quantize_beta: bool = False # model the hardware's integer occupancy reads
    record_beta: bool = True
    telemetry_noise_ppm: float = 0.0  # observation noise on *recorded* freq (Fig 16)
    seed: int = 0


@dataclasses.dataclass
class SimResult:
    """Telemetry + final state of a bittide simulation.

    freq_ppm: (T, N) recorded clock frequency offsets from nominal, ppm.
    beta: (T, E) recorded occupancies (empty if record_beta=False).
    times: (T,) physical time of each record, seconds.
    psi/nu/c_state: final simulator state (for chaining, e.g. reframing).
    """

    freq_ppm: np.ndarray
    beta: np.ndarray
    times: np.ndarray
    psi: np.ndarray
    nu: np.ndarray
    c_state: dict
    topo: Topology
    links: LinkParams
    cfg: SimConfig
    # Which engine produced this result ("segment-sum" for this module's
    # scatter-add scan; the dense Pallas runners stamp their kernel path).
    engine: str = "segment-sum"

    @property
    def final_freq_ppm(self) -> np.ndarray:
        return self.freq_ppm[-1]

    def convergence_time(self, band_ppm: float = 1.0) -> float:
        """First recorded time after which all nodes stay within band_ppm."""
        spread = self.freq_ppm.max(axis=1) - self.freq_ppm.min(axis=1)
        return _convergence_time(spread, self.times, band_ppm)


def _convergence_time(spread, times, band_ppm: float) -> float:
    """First recorded time after which a (T,) spread stays within band."""
    ok = spread <= band_ppm
    bad = np.nonzero(~ok)[0]   # last record the band was violated
    if len(bad) == 0:
        return float(times[0])
    if bad[-1] == len(ok) - 1:
        return float("inf")
    return float(times[bad[-1] + 1])


@dataclasses.dataclass
class EnsembleResult:
    """Telemetry + final state of a batched (Monte Carlo) bittide run.

    Same fields as SimResult with a leading batch axis B:
      freq_ppm: (B, T, N); beta: (B, T, E); psi/nu: (B, N);
      c_state values: (B, N).
    """

    freq_ppm: np.ndarray
    beta: np.ndarray
    times: np.ndarray
    psi: np.ndarray
    nu: np.ndarray
    c_state: dict
    topo: Topology
    links: LinkParams
    cfg: SimConfig
    engine: str = "segment-sum"

    @property
    def num_draws(self) -> int:
        return int(self.freq_ppm.shape[0])

    @property
    def final_spread_ppm(self) -> np.ndarray:
        """(B,) final recorded frequency band per draw."""
        last = self.freq_ppm[:, -1]
        return last.max(axis=1) - last.min(axis=1)

    def convergence_times(self, band_ppm: float = 1.0) -> np.ndarray:
        """(B,) first recorded time after which each draw stays in band."""
        spread = self.freq_ppm.max(axis=2) - self.freq_ppm.min(axis=2)
        return np.array([_convergence_time(s, self.times, band_ppm)
                         for s in spread])

    def draw(self, b: int) -> SimResult:
        """View draw b as a SimResult (chainable: c_state is per-draw)."""
        return SimResult(
            freq_ppm=self.freq_ppm[b], beta=self.beta[b], times=self.times,
            psi=self.psi[b], nu=self.nu[b],
            c_state={k: v[b] for k, v in self.c_state.items()},
            topo=self.topo,
            links=(self.links.draw(b) if self.links.num_draws is not None
                   else self.links),
            cfg=self.cfg, engine=self.engine)


def _run_core(src, dst, lat_frames, lam_eff, nu_u, dt_frames, inner,
              kp, beta_off, noise_ppm, noise_key, psi0, nu0, c0, edge_w,
              ctrl_mask, ctrl: ControllerConfig,
              num_nodes: int, outer: int, quantize_beta: bool,
              record_beta: bool):
    """Scan `outer` telemetry records; fori_loop `inner` control periods each.

    ``dt_frames``, ``inner``, ``kp``, ``beta_off`` and ``noise_ppm`` are
    traced (not static), so sweeps over the control period, the telemetry
    decimation, the controller gains, or the observation-noise level reuse
    one compiled executable; only topology size, ``outer`` and the
    controller/record flags key the compile cache (``ctrl`` arrives with
    its gains zeroed via ``ControllerConfig.static_key``).

    ``psi0``/``nu0``/``c0`` are the (traced) initial state — the scenario
    runner threads them across piecewise-constant segments.  ``edge_w``
    (E,) weights each edge's error contribution (0 = dropped link) and
    ``ctrl_mask`` (N,) gates the controller per node: a masked-out node
    freezes both its controller state and its ν at their previous values
    (clock holdover).  All traced, so event scenarios never recompile.
    """

    def occupancies(psi, nu):
        # ν is piecewise-constant over the period, so the delayed-phase
        # term uses the sender's current ν.
        return psi[src] - nu[src] * lat_frames + lam_eff - psi[dst]

    enabled = ctrl_mask > 0.5

    def control_period(carry):
        psi, nu, c_state = carry
        beta = occupancies(psi, nu)
        if quantize_beta:
            beta = jnp.round(beta)
        # Per-node aggregation: scatter-add (the supported successor of the
        # deprecated jax.ops.segment_sum; identical XLA scatter lowering).
        err = jnp.zeros((num_nodes,), beta.dtype).at[dst].add(
            (beta - beta_off) * edge_w)
        c_state_new, c_corr = controller_step(ctrl, c_state, err, kp)
        c_state = holdover_freeze(c_state_new, c_state, enabled)
        # (1+ν_u)(1+c) − 1 without forming 1 + O(1e-6) (f32 cancellation)
        nu_ctrl = nu_u + c_corr + nu_u * c_corr
        # Holdover: a masked-out node's ν holds its previous value.
        nu_next = jnp.where(enabled, nu_ctrl, nu)
        psi_next = psi + nu_next * dt_frames
        return (psi_next, nu_next, c_state)

    def outer_step(carry, _):
        carry = jax.lax.fori_loop(
            0, inner, lambda _, c: control_period(c), carry)
        # Read out β consistently with the post-update state.
        (psi, nu, c_state) = carry
        beta = occupancies(psi, nu)
        rec = (nu * 1e6, beta if record_beta else jnp.zeros((0,), jnp.float32))
        return carry, rec

    carry, (freq, beta) = jax.lax.scan(outer_step, (psi0, nu0, c0), None, length=outer)
    # noise_ppm == 0 adds exact zeros, so the noiseless path stays bitwise
    # identical without a recompile-keying static flag.
    freq = freq + noise_ppm * jax.random.normal(noise_key, freq.shape)
    return carry, freq, beta


_RUN_STATIC = ("ctrl", "num_nodes", "outer", "quantize_beta", "record_beta")


@functools.lru_cache(maxsize=None)
def _jitted_run():
    return partial(jax.jit, static_argnames=_RUN_STATIC)(_run_core)


def _run_ensemble_core(src, dst, lat_frames, lam_eff, nu_u, dt_frames, inner,
                       kp, beta_off, noise_ppm, noise_keys, psi0, nu0, c0,
                       edge_w, ctrl_mask, ctrl, num_nodes,
                       outer, quantize_beta, record_beta):
    """vmap of `_run_core` over a leading batch of oscillator draws.

    ``kp`` and ``beta_off`` are (B,) per-draw gains — the batched
    controller-gain axis (Fig-15-style kp sweeps in one compile).
    ``lat_frames`` / ``lam_eff`` are (B, E) per-draw link parameters
    (cable-length distributions; identical rows when shared), and
    ``psi0``/``nu0``/``c0`` per-draw initial state for segment chaining.
    ``edge_w`` and ``ctrl_mask`` are shared (E,) / (N,) rows by default
    (scenario events hit every draw at the same time); chaos campaigns
    pass per-draw (B, E) / (B, N) rows — each draw its own dropped links
    and holdover victims.
    """

    def one(lat_row, lam_row, nu_u_row, key, kp_row, boff_row, psi0_row,
            nu0_row, c0_row, w_row, m_row):
        return _run_core(src, dst, lat_row, lam_row, nu_u_row, dt_frames,
                         inner, kp_row, boff_row, noise_ppm, key, psi0_row,
                         nu0_row, c0_row, w_row, m_row, ctrl,
                         num_nodes, outer, quantize_beta, record_beta)

    w_axis = 0 if edge_w.ndim == 2 else None
    m_axis = 0 if ctrl_mask.ndim == 2 else None
    return jax.vmap(one, in_axes=(0,) * 9 + (w_axis, m_axis))(
        lat_frames, lam_eff, nu_u, noise_keys, kp, beta_off,
        psi0, nu0, c0, edge_w, ctrl_mask)


@functools.lru_cache(maxsize=None)
def _jitted_run_ensemble():
    return partial(jax.jit, static_argnames=_RUN_STATIC)(_run_ensemble_core)


def _resolve_init(init, nu_default, num_nodes: int, ctrl: ControllerConfig):
    """Initial (psi0, nu0, c0) — cold start or chained from a prior run.

    ``init`` may be None (cold start: ψ = 0, ν = ν_u, fresh controller
    state), a ``(psi, nu, c_state)`` tuple, or any result object exposing
    ``.psi`` / ``.nu`` / ``.c_state`` (SimResult, EnsembleResult) — the
    scenario runner's segment-chaining contract.  Chained state is passed
    through exactly (no re-normalization), so a split run is bit-identical
    to an unsplit one.
    """
    if init is None:
        shape = np.shape(nu_default)
        return (jnp.zeros(shape, jnp.float32), jnp.asarray(nu_default),
                controller_init(ctrl, num_nodes) if len(shape) == 1 else
                jax.tree_util.tree_map(
                    lambda z: jnp.broadcast_to(z, shape),
                    controller_init(ctrl, num_nodes)))
    if isinstance(init, (tuple, list)):
        psi, nu, c_state = init
    else:
        psi, nu, c_state = init.psi, init.nu, init.c_state
    return (jnp.asarray(psi, jnp.float32), jnp.asarray(nu, jnp.float32),
            {k: jnp.asarray(v, jnp.float32) for k, v in c_state.items()})


def _edge_node_weights(edge_w, ctrl_mask, num_edges: int, num_nodes: int,
                       num_draws: Optional[int] = None):
    """Normalize the (traced) link-drop weights and controller mask.

    Shared (E,) / (N,) rows always pass; with ``num_draws`` (ensemble
    callers) per-draw (B, E) / (B, N) rows are accepted too — the chaos
    campaigns' per-draw link-drop and holdover victims.
    """
    w = (jnp.ones((num_edges,), jnp.float32) if edge_w is None
         else jnp.asarray(edge_w, jnp.float32))
    m = (jnp.ones((num_nodes,), jnp.float32) if ctrl_mask is None
         else jnp.asarray(ctrl_mask, jnp.float32))
    w_shapes = [(num_edges,)] + (
        [(num_draws, num_edges)] if num_draws else [])
    m_shapes = [(num_nodes,)] + (
        [(num_draws, num_nodes)] if num_draws else [])
    if w.shape not in w_shapes:
        raise ValueError(f"edge_w must be one of {w_shapes}, got {w.shape}")
    if m.shape not in m_shapes:
        raise ValueError(f"ctrl_mask must be one of {m_shapes}, "
                         f"got {m.shape}")
    return w, m


def simulate(
    topo: Topology,
    links: LinkParams,
    ctrl: ControllerConfig,
    ppm_u: np.ndarray,
    cfg: SimConfig = SimConfig(),
    init=None,
    edge_w=None,
    ctrl_mask=None,
) -> SimResult:
    """Run the abstract frame model.

    Args:
      topo: network topology.
      links: per-edge physical parameters.
      ctrl: controller configuration.
      ppm_u: (N,) unadjusted oscillator offsets in ppm (paper: ±8 ppm initial
        accuracy, ±98 ppm worst-case envelope).
      cfg: simulation configuration.
      init: optional chained state — ``(psi, nu, c_state)`` or a prior
        SimResult; the scenario runner threads this across segments.
      edge_w: optional (E,) error-contribution weights (0 = dropped link).
      ctrl_mask: optional (N,) controller-enable mask (0 = clock holdover:
        the node's ν and controller state freeze).
    """
    ppm_u = np.asarray(ppm_u, np.float32)
    if ppm_u.shape != (topo.num_nodes,):
        raise ValueError(f"ppm_u must be ({topo.num_nodes},), got {ppm_u.shape}")
    if np.asarray(ctrl.kp).ndim or np.asarray(ctrl.beta_off).ndim:
        raise ValueError("simulate() takes scalar gains; per-draw kp/beta_off "
                         "arrays are the batched axis of simulate_ensemble()")
    if links.num_draws is not None:
        raise ValueError("simulate() takes a single (E,) link set; per-draw "
                         "(B, E) links are the batched axis of "
                         "simulate_ensemble()")
    inner, outer = _split_steps(cfg)
    args = _sim_arrays(topo, links, cfg)
    nu_u = jnp.asarray(ppm_u * 1e-6, jnp.float32)
    psi0, nu0, c0 = _resolve_init(init, nu_u, topo.num_nodes, ctrl)
    w, m = _edge_node_weights(edge_w, ctrl_mask, topo.num_edges,
                              topo.num_nodes)

    (psi, nu, c_state), freq, beta = _jitted_run()(
        *args, nu_u,
        jnp.float32(cfg.omega_nom * cfg.dt), jnp.int32(inner),
        jnp.float32(ctrl.kp), jnp.float32(ctrl.beta_off),
        jnp.float32(cfg.telemetry_noise_ppm), jax.random.PRNGKey(cfg.seed),
        psi0, nu0, c0, w, m,
        ctrl=ctrl.static_key(), num_nodes=topo.num_nodes, outer=outer,
        quantize_beta=cfg.quantize_beta, record_beta=cfg.record_beta)

    times = (np.arange(1, outer + 1) * inner) * cfg.dt
    return SimResult(
        freq_ppm=np.asarray(freq), beta=np.asarray(beta), times=times,
        psi=np.asarray(psi), nu=np.asarray(nu),
        c_state={k: np.asarray(v) for k, v in c_state.items()},
        topo=topo, links=links, cfg=cfg)


def _split_steps(cfg: SimConfig):
    inner = cfg.record_every
    outer = cfg.steps // inner
    if outer < 1:
        raise ValueError("steps must be >= record_every")
    return inner, outer


def _sim_arrays(topo: Topology, links: LinkParams, cfg: SimConfig):
    return (jnp.asarray(topo.src), jnp.asarray(topo.dst),
            jnp.asarray(links.latency_s * cfg.omega_nom, jnp.float32),
            jnp.asarray(links.beta0, jnp.float32))  # β(0) with ψ(0)=0


def _sim_arrays_batched(topo: Topology, links: LinkParams, cfg: SimConfig,
                        b: int):
    """(src, dst, lat (B, E), lam_eff (B, E)) with per-draw links.

    Shared (E,) link parameters are tiled to identical rows, so one vmap
    structure serves both the shared and the per-draw-links regimes.
    """
    e = topo.num_edges
    lat = np.asarray(links.latency_s, np.float64)
    b0 = np.asarray(links.beta0, np.float64)
    for name, arr in (("latency_s", lat), ("beta0", b0)):
        if arr.ndim == 2 and arr.shape != (b, e):
            raise ValueError(f"per-draw links.{name} must be (B, E) = "
                             f"({b}, {e}), got {arr.shape}")
    lat = np.broadcast_to(lat, (b, e))
    b0 = np.broadcast_to(b0, (b, e))
    return (jnp.asarray(topo.src), jnp.asarray(topo.dst),
            jnp.asarray(lat * cfg.omega_nom, jnp.float32),
            jnp.asarray(b0, jnp.float32))


def broadcast_gain(value, b: int, name: str = "kp") -> np.ndarray:
    """Normalize a controller gain to a (B,) float32 per-draw vector.

    Accepts a scalar (shared across draws) or a length-B array (one gain
    per draw — the batched gain-sweep axis).
    """
    arr = np.asarray(value, np.float32).reshape(-1)
    if arr.shape[0] == 1:
        arr = np.broadcast_to(arr, (b,))
    if arr.shape[0] != b:
        raise ValueError(
            f"{name} must be a scalar or length-{b} (one per draw), "
            f"got shape {np.asarray(value).shape}")
    return np.ascontiguousarray(arr)


def simulate_ensemble(
    topo: Topology,
    links: LinkParams,
    ctrl: ControllerConfig,
    ppm_u: np.ndarray,
    cfg: SimConfig = SimConfig(),
    init=None,
    edge_w=None,
    ctrl_mask=None,
) -> "EnsembleResult":
    """Run B independent oscillator draws in ONE compiled call.

    The batch is a ``jax.vmap`` over the same scan `simulate` runs, so one
    XLA executable serves B × steps × N node-steps — the Monte Carlo regime
    of the paper's ±8 ppm experiments (convergence-time distributions,
    worst-case envelopes) without per-draw dispatch or recompilation.

    ``ctrl.kp`` / ``ctrl.beta_off`` may be length-B arrays — one gain per
    draw.  The gains are traced per-draw state (never compile keys), so a
    Fig-15-style kp sweep is ONE compiled batched kernel: tile the same
    oscillator draw across B rows and vary only the gain.

    ``links`` may carry per-draw (B, E) ``latency_s`` / ``beta0`` — a
    cable-length distribution with one full link sample per draw (this
    lane has no class-structure restriction; every edge of every draw may
    differ).  Link parameters are traced per-draw state like the gains,
    so resampling them never recompiles.

    Args:
      ppm_u: (B, N) unadjusted oscillator offsets in ppm, one row per draw.
      init: optional chained state — ``(psi, nu, c_state)`` with (B, N)
        leaves or a prior EnsembleResult (segment chaining).
      edge_w: optional (E,) shared or (B, E) per-draw error weights
        (0 = dropped link); ctrl_mask: optional (N,) shared or (B, N)
        per-draw controller-enable mask (holdover).  Per-draw rows are
        the chaos campaigns' randomized victims — traced data, one
        compile per batch shape.

    Returns:
      EnsembleResult with leading batch axes; draw b reproduces
      ``simulate(topo, links.draw(b), ctrl, ppm_u[b], cfg)`` (with draw-b
      gains) up to vmap'd-reduction float noise (telemetry noise uses
      per-draw derived keys).
    """
    ppm_u = np.asarray(ppm_u, np.float32)
    if ppm_u.ndim != 2 or ppm_u.shape[1] != topo.num_nodes:
        raise ValueError(
            f"ppm_u must be (B, {topo.num_nodes}), got {ppm_u.shape}")
    b = ppm_u.shape[0]
    if links.num_draws is not None and links.num_draws != b:
        raise ValueError(f"links carry {links.num_draws} draws but ppm_u "
                         f"has {b}")
    inner, outer = _split_steps(cfg)
    args = _sim_arrays_batched(topo, links, cfg, b)
    keys = jax.random.split(jax.random.PRNGKey(cfg.seed), b)
    kp = broadcast_gain(ctrl.kp, b, "kp")
    beta_off = broadcast_gain(ctrl.beta_off, b, "beta_off")
    nu_u = jnp.asarray(ppm_u * 1e-6, jnp.float32)
    psi0, nu0, c0 = _resolve_init(init, nu_u, topo.num_nodes, ctrl)
    w, m = _edge_node_weights(edge_w, ctrl_mask, topo.num_edges,
                              topo.num_nodes, num_draws=b)

    (psi, nu, c_state), freq, beta = _jitted_run_ensemble()(
        *args, nu_u,
        jnp.float32(cfg.omega_nom * cfg.dt), jnp.int32(inner),
        jnp.asarray(kp), jnp.asarray(beta_off),
        jnp.float32(cfg.telemetry_noise_ppm), keys,
        psi0, nu0, c0, w, m,
        ctrl=ctrl.static_key(), num_nodes=topo.num_nodes, outer=outer,
        quantize_beta=cfg.quantize_beta, record_beta=cfg.record_beta)

    times = (np.arange(1, outer + 1) * inner) * cfg.dt
    return EnsembleResult(
        freq_ppm=np.asarray(freq), beta=np.asarray(beta), times=times,
        psi=np.asarray(psi), nu=np.asarray(nu),
        c_state={k: np.asarray(v) for k, v in c_state.items()},
        topo=topo, links=links, cfg=cfg)
