"""Sparse edge-major Pallas engine: padded-neighbor (ELL) gather-scatter.

Every dense lane pays O(N²) per control period through the (C, N, N)
adjacency stack, but all paper topologies except the 8-node fully
connected graph are bounded-degree — the abstract dynamics are a sum
over *edges* (arXiv:2109.14111; the occupancy model of arXiv:2410.05432
that ``repro.core.envelopes`` implements).  This module expresses one
control period as K slot gathers over a **slot-major ELL table**:

    nbr  (K, N) int32    nbr[k, i]  = source node of node i's k-th in-edge
    latf (·, K, N) f32   per-slot physical latency in frames
    w    (·, K, N) f32   per-slot edge weight (0 = padding / dropped link)

    err_i = Σ_k w[k,i]·(ψ[nbr[k,i]] − ν[nbr[k,i]]·latf[k,i])
            − (ψ_i + β_off)·deg_i + lamsum_i,      deg_i = Σ_k w[k,i]

followed by the cancellation-free controller update the dense kernels
use: the period body, the measurement, the guard and the output layout
are ``repro.kernels.period``'s, and this module keeps only the mirror,
the gather and the column-sliced writes.  Per-period cost is O(N·K) — for torus3d(34) (39,304 nodes,
K=6) that is ~6,500× less arithmetic than the dense formulation.  On a
TPU the VMEM-resident state then bounds the node count instead (about
5·10⁴ nodes at B = 8, see ``sparse_vmem_bytes``).

Layout: slot-major (K, N) rather than node-major (N, K), so every slot
row is an N-vector aligned with the state's lane axis and the fold is K
fused multiply-adds on (B, tile_i) tiles, never a reduction across
misaligned K lanes.  Padding slots self-index (``nbr[k, i] = i``) with
weight 0, so they gather a valid address and contribute exactly
nothing; padding *nodes* have all slots padded (degree 0) and stay inert
like the dense lanes' padding.

The kernel advances ``num_records × record_every`` periods in ONE
``pallas_call`` with grid ``(num_records, record_every, i_panels)``:
per-node state (ψ, ν) lives whole in VMEM scratch (the gather needs
every source node), while the tables stream as node panels whose index
map advances with the innermost grid axis — double-buffered from HBM
like the tiled dense engine's column panels, the slot table into SMEM
(its entries are gather addresses) and the latency/weight tables into
VMEM.

The gather.  Mosaic lowers no lane-axis gather over a whole (B, N) row
(its only gather is a within-vreg ``take_along_axis``), so the first
panel of every pass snapshots the state into a node-major *mirror*
(N, W): row j holds node j's ψ in lanes [0, B) and its ν in lanes
[B, 2B) (W = 2B rounded up to TILE), written one (TILE, TILE) transpose
at a time.  A slot's gather is then one dynamic sublane read per
destination node — ``mirror[nbr[k, r]]`` with the index from SMEM —
into a (tile_i, W) row buffer, transposed back to (2B, tile_i).  The
mirror is also the pass's snapshot of the pre-period state, so every
panel updates its own columns of the canonical carries in place.

Everything the dense lanes trace is traced here too — state, per-draw
gains, per-draw controller masks, per-draw λeff folds — plus the
latency and weight *tables themselves*: per-draw (B, K, N) tables make
per-draw LinkDrop victims (chaos campaigns) and fully heterogeneous
per-draw cable draws run on ONE compiled kernel, which no dense lane
can do (their (C, N, N) stacks are shared across draws).

β telemetry (``record_beta=True``) follows the tiled engine's scheme:
the period grid axis gains one trailing pass per record that re-streams
the tables to aggregate the post-update state's per-node net occupancy
β_i = Σ_k w·(ψ_src − ν_src·latf) − ψ_i·deg_i + lamsum_i, with ψ
mean-centered (β is shift-invariant; centering keeps float32 partial
sums O(ψ spread)).  The edge-major layout also makes a per-EDGE β
record a natural follow-on — β_e is the k-th gather term per slot
before the Σ_k fold — the record shape (K, N) is the table shape.

On CPU the kernel runs the Pallas interpreter; on TPU the same body
compiles with Mosaic (``tests/test_tpu_compile.py`` compiles it for a
v5e at Fig-18 scale, ``chip_smoke.py`` runs it on one).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.topology import Topology

from . import period
from .bittide_step import (SUBLANE, TILE, VMEM_BUDGET_BYTES, _check_shapes,
                           _lamsum_rows, _mask_row, sparse_vmem_bytes)
from .period import _gain_col

__all__ = ["bittide_sparse_pallas", "ellify", "max_in_degree"]


def max_in_degree(topo: Topology) -> int:
    """Padded slot count K the ELL tables of ``topo`` need (≥ 1)."""
    if topo.num_edges == 0:
        return 1
    return max(1, int(topo.in_degree.max()))


def ellify(topo: Topology, lat_frames, edge_w=None, tile: int = TILE,
           n_pad: Optional[int] = None, max_deg: Optional[int] = None):
    """Edge list → slot-major ELL tables for the sparse engine, placed
    on the device (:func:`ell_tables` builds them on the host).

    Args:
      topo: the directed multigraph (duplicate edges land in distinct
        slots, so multigraph weights are NOT merged — each parallel edge
        keeps its own latency, exactly like the segment-sum simulator).
      lat_frames: per-edge physical latency in frames — (E,) shared or
        (B, E) per-draw.
      edge_w: per-edge error weights — None (all 1), (E,) shared or
        (B, E) per-draw (chaos LinkDrop victims).  Weight 0 removes the
        edge from the aggregation; its slot stays allocated so dropping
        / restoring links never changes the compiled table shape.
      tile: lane quantum N pads to (TILE).
      n_pad: explicit padded node count (defaults to tile-rounded N).
      max_deg: explicit slot count K (defaults to the max in-degree;
        larger values add always-padded slots — the max-degree-padding
        edge case the property tests pin).

    Returns:
      (nbr (K, N_pad) int32, latf (R_l, K, N_pad) float32,
      w (R_w, K, N_pad) float32) with R = 1 for shared inputs or B for
      per-draw inputs (the two leading axes are independent).
    """
    return tuple(jnp.asarray(x) for x in ell_tables(
        topo, lat_frames, edge_w=edge_w, tile=tile, n_pad=n_pad,
        max_deg=max_deg))


def ell_tables(topo: Topology, lat_frames, edge_w=None, tile: int = TILE,
               n_pad: Optional[int] = None, max_deg: Optional[int] = None):
    """The tables of :func:`ellify` as host NumPy arrays."""
    n = topo.num_nodes
    e = topo.num_edges
    if n_pad is None:
        n_pad = ((n + tile - 1) // tile) * tile
    lat2 = np.atleast_2d(np.asarray(lat_frames, np.float64))
    if lat2.shape[-1] != e:
        raise ValueError(f"lat_frames must be (E,)=({e},) or (B, {e}), "
                         f"got {np.shape(lat_frames)}")
    if edge_w is None:
        w2 = np.ones((1, e), np.float64)
    else:
        w2 = np.atleast_2d(np.asarray(edge_w, np.float64))
        if w2.shape[-1] != e:
            raise ValueError(f"edge_w must be (E,)=({e},) or (B, {e}), "
                             f"got {np.shape(edge_w)}")

    dst = np.asarray(topo.dst, np.int64)
    src = np.asarray(topo.src, np.int64)
    counts = np.bincount(dst, minlength=n) if e else np.zeros(n, np.int64)
    k_need = max(1, int(counts.max())) if e else 1
    k = k_need if max_deg is None else int(max_deg)
    if k < k_need:
        raise ValueError(f"max_deg={k} < the topology's max in-degree "
                         f"{k_need}")

    # Slot assignment: each node's in-edges take slots 0..deg-1 in edge
    # order (vectorized cumcount — stable argsort groups edges by dst,
    # each edge's slot is its rank within the group).
    slot = np.zeros(e, np.int64)
    if e:
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        perm = np.argsort(dst, kind="stable")
        slot[perm] = np.arange(e) - np.repeat(starts, counts)

    # Padding slots self-index with weight 0: a valid gather address that
    # contributes nothing (padding NODES therefore stay inert: degree 0).
    nbr = np.broadcast_to(np.arange(n_pad, dtype=np.int32),
                          (k, n_pad)).copy()
    latf = np.zeros((lat2.shape[0], k, n_pad), np.float32)
    wt = np.zeros((w2.shape[0], k, n_pad), np.float32)
    if e:
        nbr[slot, dst] = src.astype(np.int32)
        latf[:, slot, dst] = lat2
        wt[:, slot, dst] = w2
    return nbr, latf, wt


def _mirror_width(b: int) -> int:
    """Lane width W of the node-major mirror: ψ and ν of B draws."""
    return -(-2 * b // TILE) * TILE


def _sparse_kernel(nbr_ref, latf_ref, w_ref, psi0_ref, nu0_ref, nu_u_ref,
                   kp_ref, boff_ref, mask_ref, lamsum_ref, *rest,
                   dt_frames: float, max_deg: int,
                   record_beta: bool, record_watermarks: bool,
                   record_guard: bool):
    t = pl.program_id(0)
    p = pl.program_id(1)
    i = pl.program_id(2)
    measure = record_beta or record_watermarks or record_guard
    periods = period.measure_periods(measure)
    r = period.unpack(rest, record_beta, record_watermarks, record_guard)
    psi_s, nu_s, mir_s, row_s = r.scratch
    b, n = psi_s.shape
    tile_i = row_s.shape[0]
    period.seed(r, psi0_ref, nu0_ref,
                jnp.logical_and(t == 0, jnp.logical_and(p == 0, i == 0)))

    def _snapshot():
        """Node-major mirror of the pass's input state (see module doc)."""
        pad = mir_s.shape[1] - 2 * b

        def chunk(c, carry):
            at = pl.multiple_of(c * TILE, TILE)
            parts = [psi_s[:, pl.ds(at, TILE)], nu_s[:, pl.ds(at, TILE)]]
            if pad:
                parts.append(jnp.zeros((pad, TILE), jnp.float32))
            mir_s[pl.ds(at, TILE), :] = jnp.concatenate(parts, axis=0).T
            return carry

        jax.lax.fori_loop(0, n // TILE, chunk, 0)

    def _gather(k: int):
        """(W, tile_i): slot k's source ψ in rows [0, B), ν in [B, 2B)."""
        def rows(r8, carry):
            # Mosaic unrolls only fully, so unroll one sublane by hand.
            for u in range(SUBLANE):
                row = r8 * SUBLANE + u
                row_s[pl.ds(row, 1), :] = mir_s[pl.ds(nbr_ref[k, row], 1), :]
            return carry

        jax.lax.fori_loop(0, tile_i // SUBLANE, rows, 0)
        return row_s[...].T

    def _step():
        cols = pl.ds(pl.multiple_of(i * tile_i, TILE), tile_i)

        @pl.when(i == 0)
        def _mirror():
            _snapshot()

        if measure:
            m = period.row_mean(psi_s)                     # (B, 1)

        # K slot gathers over the streamed (·, K, tile_i) table panel:
        # each slot row pulls its source nodes' state from the mirror
        # and folds one weighted FMA into the panel's accumulation.
        lat = latf_ref[...]                                # (·, K, TI)
        w = w_ref[...]
        deg = jnp.sum(w, axis=1)                           # (·, TI)
        acc = jnp.zeros((b, tile_i), jnp.float32)
        for k in range(max_deg):
            g = _gather(k)
            g_psi, g_nu = g[:b], g[b:2 * b]                # (B, TI)
            if measure:
                g_psi = period.centre(g_psi, m, p == periods)
            acc = acc + w[:, k, :] * (g_psi - g_nu * lat[:, k, :])

        psi_i = psi_s[:, cols]                             # (B, TI)
        nu_i = nu_s[:, cols]
        if measure:
            psi_i = period.centre(psi_i, m, p == periods)

        @pl.when(p < periods)
        def _update():
            psi_next, nu_next = period.control(
                acc, psi_i, nu_i, nu_u_ref, kp_ref, boff_ref, deg,
                lamsum_ref, mask_ref, dt_frames)
            # In place: later panels gather from the mirror's snapshot of
            # the pre-period state, never from these columns.
            psi_s[:, cols] = psi_next
            nu_s[:, cols] = nu_next
            # Telemetry flushes to HBM when the record index advances, so
            # overwriting every period within a record is decimation for
            # free.
            r.rec[0, :, cols] = nu_next
            r.psi_out[:, cols] = psi_next
            r.nu_out[:, cols] = nu_next

        if measure:
            @pl.when(p == periods)
            def _record_beta():
                # acc aggregated the centered post-update state this pass.
                bnode = acc - psi_i * deg + lamsum_ref[...]
                period.measure(r, bnode, nu_i, t, deg, cols)

    period.run(r, t, _step)


def bittide_sparse_pallas(psi, nu, nu_u, nbr, latf, w, lamsum, kp, beta_off,
                          dt_frames: float, *, num_records: int,
                          record_every: int, tile_i: Optional[int] = None,
                          ctrl_mask=None, record_beta: bool = False,
                          record_watermarks: bool = False,
                          record_guard: bool = False, guard_lo=None,
                          guard_hi=None, guard_stop=None,
                          interpret: bool = False):
    """Advance ``num_records × record_every`` periods on the ELL tables.

    Args:
      psi, nu, nu_u: (B, N) float32 state (B a multiple of SUBLANE, N a
        multiple of TILE; pad via :func:`ellify` / the ops-layer padding).
      nbr: (K, N) int32 slot-major neighbor table (see :func:`ellify`).
      latf: (1, K, N) shared or (B, K, N) per-draw slot latencies, frames.
      w: (1, K, N) shared or (B, K, N) per-draw slot weights — per-draw
        rows give each draw its own dropped links on ONE compiled kernel.
      lamsum: per-node λeff fold Σ_{e→i} w_e·λeff_e — (N,)/(1, N) shared
        or (B, N) per-draw.
      kp, beta_off: traced controller gains, scalar or per-draw length-B.
      dt_frames: static integration constant (frames per control period).
      num_records / record_every: telemetry grid (static).
      tile_i: node-panel width for streaming the tables — a multiple of
        TILE dividing N; defaults to N (single panel, tables resident).
      ctrl_mask: optional (N,)/(1, N) shared or (B, N) per-draw
        controller-enable mask (0 = clock holdover).  Traced.
      record_beta: also decimate the per-node net occupancy (frames) to
        every record — one extra table pass per record (compile-time
        switch; the ν-only grid is unchanged when off).
      record_watermarks: carry O(B·N) excursion watermarks in-kernel —
        per-node max |β|, its record index, and the ν min/max — updated
        at every record from the same β aggregation pass, so a run
        reports its peak excursion with NO (R, B, N) record.  Shares
        the extra table pass with ``record_beta`` when both are on.
      record_guard: in-kernel reframing guard with chunk early-exit —
        shares the measure pass, adds a (B, 1) int32 first-trip-record
        output and freezes all records after the earliest trip (or past
        the traced ``guard_stop`` cap).  See
        :func:`repro.kernels.bittide_step.bittide_fused_pallas`.
      guard_lo, guard_hi, guard_stop: traced guard band (frames per unit
        weighted degree, scalar or per-draw) and stop-after record index;
        required with ``record_guard``.
      interpret: run in interpret mode (CPU validation).

    Returns:
      :class:`repro.kernels.EngineOutputs` — the fused engines' contract:
      (psi_final (B, N), nu_final (B, N), freq = nu_rec
      (num_records, B, N), beta = beta_rec or None, watermarks or None,
      guard_state (B, 1) int32 or None); watermarks = (beta_abs_max
      (B, N) f32, peak_record (B, N) i32, nu_min (B, N) f32, nu_max
      (B, N) f32).
    """
    b, n = psi.shape
    _check_shapes(b, n, num_records, record_every)
    k = nbr.shape[0]
    if nbr.shape != (k, n):
        raise ValueError(f"nbr must be (K, {n}), got {nbr.shape}")
    for name, tbl in (("latf", latf), ("w", w)):
        if tbl.ndim != 3 or tbl.shape[1:] != (k, n) \
                or tbl.shape[0] not in (1, b):
            raise ValueError(f"{name} must be (1, {k}, {n}) or "
                             f"({b}, {k}, {n}), got {jnp.shape(tbl)}")
    if tile_i is None:
        tile_i = n
    if tile_i < TILE or tile_i % TILE or n % tile_i:
        raise ValueError(
            f"tile_i={tile_i} must be a multiple of {TILE} dividing N={n}")
    i_panels = n // tile_i
    rows = max(latf.shape[0], w.shape[0])
    vmem = sparse_vmem_bytes(b, n, k, tile_i, rows, record_beta=record_beta,
                             record_watermarks=record_watermarks,
                             record_guard=record_guard)
    if vmem > VMEM_BUDGET_BYTES and not interpret:
        raise ValueError(
            f"sparse working set {vmem/2**20:.1f} MiB exceeds the "
            f"{VMEM_BUDGET_BYTES/2**20:.0f} MiB VMEM budget (B={b}, N={n}, "
            f"K={k}, tile_i={tile_i}); the O(B·N) state must stay resident "
            "— shard the node axis or use the segment-sum simulator")

    kern = functools.partial(
        _sparse_kernel, dt_frames=float(dt_frames), max_deg=int(k))
    mask = _mask_row(ctrl_mask, n, b)
    whole = period.whole
    panel2 = lambda t, p, i: (0, i)
    in_specs = [
        # Table panels: the index map advances with i, so the Pallas
        # pipeline double-buffers the HBM fetch of panel i+1 behind
        # the gathers on panel i.  Slot entries are gather addresses,
        # read as scalars, so their panel lands in SMEM.
        pl.BlockSpec((k, tile_i), panel2,
                     memory_space=pltpu.SMEM),            # nbr
        pl.BlockSpec((latf.shape[0], k, tile_i),
                     lambda t, p, i: (0, 0, i)),          # latf
        pl.BlockSpec((w.shape[0], k, tile_i),
                     lambda t, p, i: (0, 0, i)),          # w
        pl.BlockSpec((b, n), whole),                      # psi0
        pl.BlockSpec((b, n), whole),                      # nu0
        pl.BlockSpec((b, tile_i), panel2),                # nu_u
        pl.BlockSpec((b, 1), whole),                      # kp per draw
        pl.BlockSpec((b, 1), whole),                      # beta_off
        pl.BlockSpec((mask.shape[0], tile_i), panel2),    # ctrl mask
        pl.BlockSpec((b, tile_i), panel2),                # lamsum
    ]
    args = [nbr.astype(jnp.int32), latf.astype(jnp.float32),
            w.astype(jnp.float32), psi.astype(jnp.float32),
            nu.astype(jnp.float32), nu_u.astype(jnp.float32),
            _gain_col(kp, b, "kp"), _gain_col(beta_off, b, "beta_off"),
            mask, _lamsum_rows(lamsum, b, n)]
    measure = record_beta or record_watermarks or record_guard
    return period.launch(
        kern, name="bittide_sparse",
        grid=(num_records, record_every + (1 if measure else 0), i_panels),
        in_specs=in_specs, args=args,
        scratch=[
            pltpu.VMEM((b, n), jnp.float32),                      # ψ carry
            pltpu.VMEM((b, n), jnp.float32),                      # ν carry
            pltpu.VMEM((n, _mirror_width(b)), jnp.float32),       # mirror
            pltpu.VMEM((tile_i, _mirror_width(b)), jnp.float32),  # gathered
        ],
        b=b, n=n, num_records=num_records, record_beta=record_beta,
        record_watermarks=record_watermarks, record_guard=record_guard,
        guard_lo=guard_lo, guard_hi=guard_hi, guard_stop=guard_stop,
        interpret=interpret)
