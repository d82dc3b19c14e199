"""Pallas TPU kernels: fused bittide control-period stepping.

This is the compute hot-spot of large-scale bittide simulation (the paper
simulates 22^3-node networks in Callisto, Fig 18; the FPGA evaluates the
same update per-frame in hardware).  The GPU-ish formulation would be an
edge-list gather/scatter; TPUs want dense tiles, so the network is
expressed as a small stack of (N, N) adjacency masks — one per physical-
latency class — and one control period is computed as matvecs +
elementwise ops entirely in VMEM:

    err_i = Σ_c [A_c @ (ψ − ν·lat_c)]_i  −  (ψ_i + β_off)·deg_i  +  lamsum_i
    ν'_i  = (1 + ν_u_i)(1 + kp·err_i) − 1
    ψ'_i  = ψ_i + ν'_i·Δt

where deg_i = Σ_{c,j} A[c,i,j] and lamsum_i = Σ_{c,j} λeff[c,i,j] are
step-invariant and precomputed once (they fold the per-edge λeff and β_off
terms into per-node constants — this algebraic refactor is what removes the
need to ever materialize the (C, N, N) occupancy tensor β).

Three lanes are provided.  They share one period body —
``repro.kernels.period`` holds the controller law, the β measurement,
the watermarks, the guard, the early exit and the wrapper-side output
layout — and each keeps only how it aggregates the adjacency:

``bittide_step_pallas``
    One control period, grid (N/TILE, N/TILE), err accumulated in the ν'
    output block across the j axis.  Kept as the per-step baseline and for
    N too large to hold (C, N, N) in VMEM at once.

``bittide_fused_pallas``
    The resident engine: ONE ``pallas_call`` advances ``num_records ×
    record_every`` control periods for a whole batch of B independent
    oscillator draws.  The grid iterates over telemetry records (TPU grids
    execute sequentially); the (B, N) state lives in VMEM *scratch* that
    persists across grid steps, the adjacency stack and per-node invariants
    stay resident (their index maps are constant, so the blocks are fetched
    once), and each grid step runs ``record_every`` periods with an
    in-kernel ``fori_loop`` — telemetry is decimated in-kernel, so ν is
    written back to HBM once per record instead of once per period.  The
    per-period matvec becomes a (B, N) × (N, N) matmul, which is exactly
    the MXU's shape.  This removes the per-period kernel-launch + HBM
    round-trip that dominated the old ``lax.scan``-of-``pallas_call`` path.

``bittide_tiled_fused_pallas``
    The tiled engine for networks whose (C, N, N) adjacency does NOT fit
    in VMEM (Fig-18-scale tori).  The grid gains two inner dimensions,
    ``(num_records, record_every, j_tiles)``: the period loop moves from
    an in-kernel ``fori_loop`` into the grid, and each period accumulates
    its aggregation over (C, N, TILE_J) column panels of the adjacency.
    The Pallas pipeline streams the panels from HBM with double buffering
    (the panel index map advances every grid step, so the next panel's DMA
    overlaps the current panel's matmul); only the panel, the (B, N) state
    scratch and an accumulator are VMEM-resident.  With a single j tile
    (TILE_J == N) it degenerates to the resident engine's schedule minus
    the in-kernel period loop.

The sparse ELL lane (``repro.kernels.bittide_sparse``) is the third user
of the shared body.

Controller gains (``kp``, ``beta_off``) are *traced per-draw inputs* of
shape (B, 1) in both engines — never compile-time constants — so Fig-15
style gain sweeps batch along B and compile exactly once.

The scenario subsystem (``repro.scenarios``) extends that principle to the
physical link parameters and the controller topology itself: the per-class
latencies are a traced (B, C) input (per-draw cable-length distributions),
the per-node λeff fold ``lamsum`` is a traced (B, N) input (per-draw /
per-segment logical-latency constants), and a per-node controller-enable
mask ``ctrl_mask`` ((1, N) shared or (B, N) per-draw — chaos campaigns
give each draw its own holdover victims) gates the frequency update — a
masked node's ν is *held* at its previous value (clock holdover) instead
of recomputed.
None of these key a compile, so a multi-event scenario replays ONE
compiled kernel across all of its piecewise-constant segments.

State layout: B is the sublane axis (pad to a multiple of 8 for float32),
N the lane axis (pad to a multiple of 128); padding nodes have degree 0 and
stay inert, padding batch rows are dead weight.

β telemetry (``record_beta=`` / ``emit_beta=``)
-----------------------------------------------
The paper's headline hardware result is *bounded buffer excursions*
(Figs. 12–14, 17–19), so the kernels can record the occupancy alongside ν.
In relative coordinates the per-edge occupancy is a pure function of the
instantaneous state (see ``repro.core.frame_model``):

    β_e = ψ_src − ν_src·ω·l_e + λeff_e − ψ_dst        [frames]

The dense kernels never materialize the (C, N, N) β tensor; what they CAN
emit for free-ish is the **per-node net occupancy** — the same aggregation
the controller already computes, minus the setpoint term:

    β_i = Σ_{e→i} w_e·β_e = Σ_c [A_c @ (ψ − ν·lat_c)]_i − ψ_i·deg_i + lamsum_i

With ``record_beta=True`` the fused engines evaluate this at every record
point from the *post-update* state (the segment-sum recording convention)
and emit it as a second decimated telemetry stream.  For float32 accuracy
the record computation centers ψ by its mean first — β is exactly
invariant under a uniform ψ shift, and centering keeps the matmul partial
sums O(ψ spread) instead of O(ψ magnitude).  Cost: one extra C-class
aggregation per *record* (not per period) — the resident engine reuses the
VMEM-resident adjacency, the tiled engine appends one extra j-panel sweep
per record to its grid, so the ν-only fast path is untouched when the
flag is off (it is a compile-time switch, not a traced branch).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import period
from .period import COMPILER_PARAMS, VMEM_LIMIT_BYTES, _gain_col

__all__ = ["bittide_step_pallas", "bittide_fused_pallas", "Incidence",
           "incidence",
           "bittide_tiled_fused_pallas", "select_engine", "fused_vmem_bytes",
           "tiled_vmem_bytes", "sparse_vmem_bytes", "TILE", "SUBLANE",
           "sparse_panel", "VMEM_LIMIT_BYTES", "VMEM_BUDGET_BYTES",
           "RESIDENT_N_MAX", "TILE_J_MAX", "COMPILER_PARAMS"]

TILE = 128     # MXU/VPU-aligned tile edge (lane axis)
SUBLANE = 8    # float32 sublane quantum (batch axis of the fused kernel)

# What the estimators below may fill: the remaining quarter is headroom
# for Mosaic's internal scratch (spilled (B, N) temporaries).
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES * 3 // 4
# Full-f32 MXU passes for every aggregation: at Mosaic's default f32
# contraction precision the fused lane ran 4e-3 ppm (a bf16-scale error)
# from segment-sum on a v5e — ψ is not mean-centred in the period update.
_F32_MXU = jax.lax.Precision.HIGHEST

# --- tile-size heuristic for engine dispatch (see `select_engine`) -------
# Keep the whole (C, N, N) adjacency VMEM-resident only up to this padded
# N.  Beyond it the tiled engine streams (C, N, TILE_J) column panels:
# residency stops paying once the stack dominates VMEM, while streaming
# bounds the footprint and leaves headroom for batch/gain axes.  The
# trade-off is that streamed panels are re-fetched every control period —
# the cutoffs have not been measured on a chip yet; tuning them against
# measured HBM bandwidth is a ROADMAP item.
RESIDENT_N_MAX = 2 * TILE
# Widest streamed panel (2 MXU tiles): wide enough to amortize the DMA,
# narrow enough that the double-buffered pair stays a small VMEM fraction.
TILE_J_MAX = 2 * TILE


def _kernel(lat_ref, a_ref, psi_j_ref, nu_j_ref, psi_i_ref, nu_i_ref,
            nu_u_ref, mask_ref, deg_ref, lamsum_ref, psi_out_ref, nu_out_ref,
            *opt_refs, kp: float, beta_off: float, dt_frames: float,
            num_classes: int, j_tiles: int, emit_beta: bool):
    j = pl.program_id(1)

    # Partial Σ_c A_c @ (ψ_j − ν_j·lat_c) for this (i, j) tile.
    acc = jnp.zeros((1, psi_i_ref.shape[-1]), jnp.float32)
    for c in range(num_classes):
        x = psi_j_ref[...] - nu_j_ref[...] * lat_ref[c, 0]        # (1, TJ)
        partial = jax.lax.dot_general(
            a_ref[c], x[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=_F32_MXU,
            preferred_element_type=jnp.float32)                    # (TI,)
        acc = acc + partial[None, :]

    # Accumulate across j tiles in the ν' output block (index map is
    # i-only, so the same VMEM block is revisited for every j).
    @pl.when(j == 0)
    def _init():
        nu_out_ref[...] = acc

    @pl.when(j > 0)
    def _acc():
        nu_out_ref[...] += acc

    # Last j tile: fold per-node invariants, apply controller, integrate.
    @pl.when(j == j_tiles - 1)
    def _finalize():
        if emit_beta:
            # Per-node net occupancy of the INPUT state: the accumulated
            # aggregation is still in the ν' output block at this point.
            opt_refs[0][...] = (nu_out_ref[...]
                                - psi_i_ref[...] * deg_ref[...]
                                + lamsum_ref[...])
        psi_out_ref[...], nu_out_ref[...] = period.control(
            nu_out_ref, psi_i_ref, nu_i_ref, nu_u_ref, kp, beta_off,
            deg_ref, lamsum_ref, mask_ref, dt_frames)


def bittide_step_pallas(psi, nu, nu_u, a, lam_eff, lat_frames,
                        kp: float, beta_off: float, dt_frames: float,
                        *, ctrl_mask=None, emit_beta: bool = False,
                        interpret: bool = False):
    """One fused bittide control period (per-step baseline kernel).

    Args:
      psi, nu, nu_u: (N,) float32 node state (N a multiple of TILE; pad via
        `repro.kernels.ops.densify`, padded nodes have degree 0).
      a: (C, N, N) float32 adjacency masks per latency class.
      lam_eff: (C, N, N) float32 per-edge effective logical latencies.
      lat_frames: (C,) float32 per-class physical latency in frames.
      kp, beta_off, dt_frames: static controller/integration constants.
      ctrl_mask: optional (N,) float32 controller-enable mask; nodes with
        mask 0 hold their previous ν (clock holdover).  None = all enabled.
      emit_beta: also output the per-node net occupancy (frames) of the
        *input* state, Σ_{e→i} w_e·β_e — β is a pure function of state, so
        the per-step record lane calls the kernel once more on the
        post-update state (ψ pre-centered by the caller) to record it.
        Compile-time switch: the two-output fast path is unchanged.
      interpret: run the kernel body in interpret mode (CPU validation).

    Returns:
      (psi_next, nu_next), both (N,) float32; with ``emit_beta`` a third
      element beta_node (N,) float32.
    """
    n = psi.shape[0]
    c = a.shape[0]
    if n % TILE:
        raise ValueError(f"N={n} must be a multiple of {TILE}")
    i_tiles = j_tiles = n // TILE

    # Step-invariant per-node folds.
    deg = a.sum(axis=(0, 2))
    lamsum = lam_eff.sum(axis=(0, 2))
    if ctrl_mask is None:
        ctrl_mask = jnp.ones((n,), jnp.float32)

    def row(v):  # 2-D (1, N) layout for TPU-friendly vector tiles
        return v.reshape(1, n).astype(jnp.float32)

    kern = functools.partial(
        _kernel, kp=float(kp), beta_off=float(beta_off),
        dt_frames=float(dt_frames), num_classes=int(c), j_tiles=j_tiles,
        emit_beta=bool(emit_beta))

    out_specs = [
        pl.BlockSpec((1, TILE), lambda i, j: (0, i)),            # psi'
        pl.BlockSpec((1, TILE), lambda i, j: (0, i)),            # nu' (accum)
    ]
    out_shape = [
        jax.ShapeDtypeStruct((1, n), jnp.float32),
        jax.ShapeDtypeStruct((1, n), jnp.float32),
    ]
    if emit_beta:
        out_specs.append(pl.BlockSpec((1, TILE), lambda i, j: (0, i)))
        out_shape.append(jax.ShapeDtypeStruct((1, n), jnp.float32))

    out = pl.pallas_call(
        kern,
        name="bittide_step",
        grid=(i_tiles, j_tiles),
        in_specs=[
            pl.BlockSpec((c, 1), lambda i, j: (0, 0)),           # lat (C,1)
            pl.BlockSpec((c, TILE, TILE), lambda i, j: (0, i, j)),  # A
            pl.BlockSpec((1, TILE), lambda i, j: (0, j)),        # psi_j
            pl.BlockSpec((1, TILE), lambda i, j: (0, j)),        # nu_j
            pl.BlockSpec((1, TILE), lambda i, j: (0, i)),        # psi_i
            pl.BlockSpec((1, TILE), lambda i, j: (0, i)),        # nu_i
            pl.BlockSpec((1, TILE), lambda i, j: (0, i)),        # nu_u
            pl.BlockSpec((1, TILE), lambda i, j: (0, i)),        # ctrl mask
            pl.BlockSpec((1, TILE), lambda i, j: (0, i)),        # deg
            pl.BlockSpec((1, TILE), lambda i, j: (0, i)),        # lamsum
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(lat_frames.reshape(c, 1).astype(jnp.float32),
      a.astype(jnp.float32), row(psi), row(nu), row(psi), row(nu),
      row(nu_u), row(jnp.asarray(ctrl_mask, jnp.float32)),
      row(deg), row(lamsum))
    if emit_beta:
        return out[0][0], out[1][0], out[2][0]
    return out[0][0], out[1][0]


def _fused_kernel(lat_ref, a_ref, psi0_ref, nu0_ref, nu_u_ref, kp_ref,
                  boff_ref, mask_ref, deg_ref, lamsum_ref, *rest,
                  dt_frames: float, record_every: int, num_classes: int,
                  record_beta: bool, record_watermarks: bool,
                  record_guard: bool, pulses=None, integer: bool = False):
    t = pl.program_id(0)
    edge_refs, rest = (rest[:4], rest[4:]) if integer else ((), rest)
    c0_ref, rest = (rest[0], rest[1:]) if pulses else (None, rest)
    r = period.unpack(rest, record_beta, record_watermarks, record_guard,
                      pulses is not None)
    psi_s, nu_s = r.scratch[:2]
    period.seed(r, psi0_ref, nu0_ref, t == 0, c0_ref)

    nu_u = nu_u_ref[...]        # (B, N), resident across the whole run
    deg = deg_ref[...]          # (1, N), broadcasts over B
    lamsum = lamsum_ref[...]    # (B, N) per-draw λeff fold
    kp = kp_ref[...]            # (B, 1) traced per-draw gains
    beta_off = boff_ref[...]
    lat = lat_ref[...]          # (B, C) traced per-draw class latencies
    enabled = mask_ref[...] > 0.5   # (1, N)|(B, N) controller-enable mask
    measure = record_beta or record_watermarks or record_guard

    def aggregate(psi, nu):
        acc = jnp.zeros_like(psi)
        for c in range(num_classes):
            x = psi - nu * lat[:, c:c + 1]                        # (B, N)
            # acc[b, i] += Σ_j A[c, i, j] · x[b, j]  — an MXU matmul.
            acc = acc + jax.lax.dot_general(
                x, a_ref[c],
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=_F32_MXU,
                preferred_element_type=jnp.float32)
        return acc

    if integer:
        src_ref, dst_ref, scat_ref, lame_ref = edge_refs
        lam_e = lame_ref[...]   # (1, E)|(B, E) per-edge λeff

        def contract(x, m, **precision):
            # out[b, k] = Σ_j m[k, j] · x[b, j]  — an MXU matmul.
            return jax.lax.dot_general(
                x, m, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, **precision)

        def gather(x, m):
            # A one-hot bf16 row selects x[b, j] exactly: x is split into
            # three bf16 parts, x = hi + mid + lo exactly, each selected by
            # one single-pass contraction and summed back exactly in f32
            # (half the passes of a Precision.HIGHEST contraction).
            hi = x.astype(jnp.bfloat16)
            r = x - hi.astype(jnp.float32)
            mid = r.astype(jnp.bfloat16)
            lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            return (contract(hi, m) + contract(mid, m)) + contract(lo, m)

        def readout(psi, nu):
            # Σ_{e→i} w_e·rint(β_e): each edge formed and rounded alone.
            src_term = gather(psi - nu * lat[:, 0:1], src_ref[0])
            for c in range(1, num_classes):
                src_term = src_term + gather(psi - nu * lat[:, c:c + 1],
                                             src_ref[c])
            beta_e = period.read_edges(src_term, lam_e,
                                       gather(psi, dst_ref[...]))
            return contract(beta_e, scat_ref[...], precision=_F32_MXU)
    else:
        readout = aggregate

    def one_period(_, carry):
        psi, nu = carry[:2]
        return period.control(readout(psi, nu), psi, nu, nu_u, kp,
                              beta_off, deg, lamsum, enabled, dt_frames,
                              pulses, carry[2] if pulses else None, integer)

    def _advance():
        carry = (psi_s[...], nu_s[...])
        if pulses:
            carry += (r.scratch[2][...],)
        carry = jax.lax.fori_loop(0, record_every, one_period, carry)
        psi, nu = carry[:2]
        psi_s[...] = psi
        nu_s[...] = nu
        if pulses:
            r.scratch[2][...] = carry[2]
            r.c_out[...] = carry[2]

        # Decimated telemetry: ν once per record, not once per period.
        r.rec[...] = nu[None]
        if measure:
            # Per-node net occupancy of the POST-update state (the
            # segment-sum recording convention), from one extra
            # aggregation per RECORD on the resident adjacency —
            # ~1/record_every of the period loop's matmul work.  The
            # watermarks and the guard read the SAME aggregation, so the
            # in-kernel peak is bit-identical to a reduction of the full
            # β record.
            psi_c = psi - period.row_mean(psi)
            bnode = aggregate(psi_c, nu) - psi_c * deg + lamsum
            period.measure(r, bnode, nu, t, deg)
        r.psi_out[...] = psi
        r.nu_out[...] = nu

    period.run(r, t, _advance)


def _block_bytes(*shape: int) -> int:
    """VMEM bytes of one 32-bit block: its last two dims pad to the
    (SUBLANE, TILE) tile, so a (1, N) row costs as much as (8, N)."""
    *lead, rows, cols = shape
    size = 4 * (-(-rows // SUBLANE) * SUBLANE) * (-(-cols // TILE) * TILE)
    for d in lead:
        size *= d
    return size


def _working_set(operands, scratch) -> int:
    """Pipelined operands (every blocked input and output) get two
    buffers each — even constant-index blocks; scratch gets one."""
    return (2 * sum(_block_bytes(*s) for s in operands)
            + sum(_block_bytes(*s) for s in scratch))


def _state_operands(b: int, n: int, panel: int, record_beta: bool,
                    record_watermarks: bool, record_guard: bool):
    """The blocks every batched lane pipelines besides its adjacency:
    ψ0/ν0 whole rows, per-node ``panel``-wide ν_u / mask / λeff fold,
    gain columns, whole-row ψ/ν/ν-record outputs, and the telemetry
    variant's β record block, four (B, N) watermark accumulators, and
    guard band / stop inputs plus the trip output."""
    ops = [(b, n), (b, n), (b, panel), (b, panel), (b, panel),
           (b, 1), (b, 1), (b, n), (b, n), (1, b, n)]
    if record_beta:
        ops.append((1, b, n))
    if record_watermarks:
        ops += [(b, n)] * 4
    if record_guard:
        ops += [(b, 1)] * 4
    return ops


def fused_vmem_bytes(b: int, n: int, c: int, *, record_beta: bool = False,
                     record_watermarks: bool = False,
                     record_guard: bool = False, edges: int = 0,
                     pulses: bool = False) -> int:
    """Working set of the fused kernel variant: the resident (C, N, N)
    stack, state, telemetry outputs and the ψ/ν scratch carries.  Integer
    readout over ``edges`` (padded E, 0 = continuous) adds the (C, E, N)
    source and (E, N) destination incidence, the (N, E) weighted scatter
    and the (B, E) per-edge λeff (each counted at 32 bits, so the bf16
    one-hot matrices are over-counted); the FINC/FDEC variant
    (``pulses``) the ``c_est`` input, output and carry."""
    ops = [(b, c), (c, n, n), (1, n)] + _state_operands(
        b, n, n, record_beta, record_watermarks, record_guard)
    scratch = [(b, n)] * 2
    if edges:
        ops += [(c, edges, n), (edges, n), (n, edges), (b, edges)]
    if pulses:
        ops += [(b, n)] * 2
        scratch.append((b, n))
    return _working_set(ops, scratch)


def tiled_vmem_bytes(b: int, n: int, c: int, tile_j: int, *,
                     record_beta: bool = False,
                     record_watermarks: bool = False,
                     record_guard: bool = False) -> int:
    """Working set of the tiled kernel variant: one double-buffered
    (C, N, tile_j) column panel instead of the whole stack, plus state,
    telemetry outputs and the ψ/ν/accumulator scratch."""
    ops = [(b, c), (c, n, tile_j), (1, n)] + _state_operands(
        b, n, n, record_beta, record_watermarks, record_guard)
    return _working_set(ops, [(b, n)] * 3)


def sparse_vmem_bytes(b: int, n: int, k: int, tile_i: int,
                      table_rows: int = 1, *, record_beta: bool = False,
                      record_watermarks: bool = False,
                      record_guard: bool = False) -> int:
    """Working set of the sparse ELL kernel variant.

    The ψ/ν carries stay whole-row resident and the gather reads a
    node-major (N, W) mirror of them (W = 2B lanes rounded up to TILE)
    through a (tile_i, W) row buffer; the latency/weight tables stream
    as (·, K, tile_i) panels (``table_rows`` is their leading axis: 1
    shared, B per-draw).  The slot table itself streams into SMEM and
    costs no VMEM.
    """
    w = -(-2 * b // TILE) * TILE
    ops = [(table_rows, k, tile_i)] * 2 + _state_operands(
        b, n, tile_i, record_beta, record_watermarks, record_guard)
    return _working_set(ops, [(b, n), (b, n), (n, w), (tile_i, w)])


def select_engine(b: int, n: int, c: int,
                  vmem_budget: int = VMEM_BUDGET_BYTES,
                  max_deg=None, *, record_beta: bool = False,
                  record_watermarks: bool = False,
                  record_guard: bool = False, edges: int = 0,
                  pulses: bool = False):
    """Tile-size dispatch heuristic: (engine, tile_j) for padded (B, N, C).

    Replaces the old VMEM cliff (fused-or-per-step-fallback) with four
    regimes:

    - ``("fused", n)`` — the whole adjacency stays VMEM-resident and is
      fetched once (n ≤ RESIDENT_N_MAX and the resident set fits).
    - ``("tiled", tj)`` — adjacency streamed as (C, N, tj) column panels,
      double-buffered from HBM; tj is the widest multiple of TILE that
      divides n, is at most TILE_J_MAX, and fits the budget.
    - ``("sparse", ti)`` — only reachable when the caller supplies
      ``max_deg`` (the padded in-degree K of the ELL tables): per-period
      cost drops from O(N²) to O(N·K) with the slot-major neighbor
      tables streamed in (·, K, ti) node panels.  Chosen when every dense
      working set is over budget but the O(B·N) resident state still
      fits — bounded-degree graphs past the dense lanes' reach.
    - ``("per-step", 0)`` — nothing fits (huge C·N, no degree bound);
      the per-period tiled 2-D kernel is the only option left.

    Callers without neighbor-table information omit ``max_deg`` and get
    the historical three-regime behavior unchanged.  The telemetry flags
    name the kernel variant that will run: its β record, watermark and
    guard buffers count against the budget, so the lane and tile chosen
    are the ones that compile for that variant.  So do integer readout
    over ``edges`` and the FINC/FDEC ``pulses``, which only the fused
    lane runs: their operands count against its budget, and where it
    does not fit the lane returned is one that refuses them.
    """
    tel = dict(record_beta=record_beta, record_watermarks=record_watermarks,
               record_guard=record_guard)
    if n <= RESIDENT_N_MAX and fused_vmem_bytes(
            b, n, c, **tel, edges=edges, pulses=pulses) <= vmem_budget:
        return "fused", n
    tj = min(n, TILE_J_MAX)
    while tj >= TILE:
        if n % tj == 0 and tiled_vmem_bytes(b, n, c, tj,
                                            **tel) <= vmem_budget:
            return "tiled", tj
        tj -= TILE
    if max_deg is not None:
        ti = sparse_panel(b, n, int(max_deg), vmem_budget=vmem_budget, **tel)
        if ti:
            return "sparse", ti
    return "per-step", 0


def sparse_panel(b: int, n: int, k: int, table_rows: int = 1,
                 vmem_budget: int = VMEM_BUDGET_BYTES, **telemetry):
    """Node-panel width of the sparse ELL kernel variant: the widest
    multiple of TILE dividing padded N, at most TILE_J_MAX, whose working
    set fits ``vmem_budget`` — or None when none does.  Wider panels only
    grow the gather's (tile_i, W) row buffer and its transposes.  The
    choice is the same under the interpreter as on the chip, so CPU runs
    take the panel layout the chip will.  ``telemetry`` holds the
    kernel's ``record_*`` flags."""
    ti = min(n, TILE_J_MAX)
    while ti >= TILE:
        if n % ti == 0 and sparse_vmem_bytes(
                b, n, k, ti, table_rows, **telemetry) <= vmem_budget:
            return ti
        ti -= TILE
    return None


def _lat_rows(lat_frames, b: int, c: int):
    """Normalize per-class latencies — (C,) shared or (B, C) per-draw —
    to the (B, C) traced input the fused kernels consume."""
    lat = jnp.asarray(lat_frames, jnp.float32)
    if lat.ndim == 1:
        lat = jnp.broadcast_to(lat.reshape(1, -1), (b, lat.shape[0]))
    if lat.shape != (b, c):
        raise ValueError(f"lat_frames must be ({c},) or ({b}, {c}), "
                         f"got {jnp.shape(lat_frames)}")
    return lat


def _lamsum_rows(lamsum, b: int, n: int):
    """Normalize the per-node λeff fold — (N,)/(1, N) shared or (B, N)
    per-draw — to the (B, N) traced input the fused kernels consume."""
    ls = jnp.asarray(lamsum, jnp.float32)
    if ls.ndim == 1 or ls.shape[0] == 1:
        ls = jnp.broadcast_to(ls.reshape(1, n), (b, n))
    if ls.shape != (b, n):
        raise ValueError(f"lamsum must be ({n},), (1, {n}) or ({b}, {n}), "
                         f"got {jnp.shape(lamsum)}")
    return ls


def _mask_row(ctrl_mask, n: int, b: int = 1):
    """Normalize the controller-enable mask to (1, N) shared or (B, N)
    per-draw float32 rows (each draw its own holdover victims)."""
    if ctrl_mask is None:
        return jnp.ones((1, n), jnp.float32)
    mask = jnp.asarray(ctrl_mask, jnp.float32)
    if mask.ndim == 1:
        mask = mask.reshape(1, -1)
    if mask.shape not in ((1, n), (b, n)):
        raise ValueError(f"ctrl_mask must be ({n},), (1, {n}) or "
                         f"({b}, {n}), got {jnp.shape(ctrl_mask)}")
    return mask


def _check_shapes(b, n, num_records, record_every):
    if n % TILE:
        raise ValueError(f"N={n} must be a multiple of {TILE}")
    if b % SUBLANE:
        raise ValueError(f"B={b} must be a multiple of {SUBLANE}")
    if num_records < 1 or record_every < 1:
        raise ValueError("num_records and record_every must be >= 1")


class Incidence(NamedTuple):
    """A fabric's edges as one-hot matrices, for integer readout on the
    fused lane (E padded to a multiple of TILE; padding edges are zero
    rows and columns):

    src:  (C, E, N) bf16 — src[c, e, j] = 1 where edge e leaves node j
          and has latency class c;
    dst:  (E, N) bf16    — dst[e, i] = 1 where edge e enters node i;
    scat: (N, E) f32     — scat[i, e] = w_e where edge e enters node i.

    0 and 1 are exact in bfloat16, so the gathers take single-pass
    contractions; the weights may be any float32.
    """
    src: object
    dst: object
    scat: object


def incidence(src, dst, cls, w, c: int, n: int) -> Incidence:
    """Host :class:`Incidence` of E edges (``src``, ``dst``, class
    ``cls``, weight ``w``, each (E,)) over C classes and N padded nodes."""
    e = len(src)
    e_pad = max(TILE, -(-e // TILE) * TILE)
    ix = np.arange(e)
    s = np.zeros((c, e_pad, n), jnp.bfloat16)
    s[np.asarray(cls), ix, np.asarray(src)] = 1
    d = np.zeros((e_pad, n), jnp.bfloat16)
    d[ix, np.asarray(dst)] = 1
    sc = np.zeros((n, e_pad), np.float32)
    sc[np.asarray(dst), ix] = np.asarray(w, np.float32)
    return Incidence(s, d, sc)


def _whole_map(ndim: int):
    """Index map of a block that is the whole ``ndim``-axis array."""
    return lambda *_: (0,) * ndim


def bittide_fused_pallas(psi, nu, nu_u, a, deg, lamsum, lat_frames,
                         kp, beta_off, dt_frames: float,
                         *, num_records: int, record_every: int,
                         ctrl_mask=None, record_beta: bool = False,
                         record_watermarks: bool = False,
                         record_guard: bool = False, guard_lo=None,
                         guard_hi=None, guard_stop=None,
                         interpret: bool = False,
                         pulses: Optional[period.Pulses] = None,
                         c_est=None, edges: Optional[Incidence] = None,
                         lam_edges=None):
    """Advance ``num_records * record_every`` control periods in ONE kernel.

    Args:
      psi, nu, nu_u: (B, N) float32 state for B independent oscillator
        draws (B a multiple of SUBLANE, N a multiple of TILE).
      a: (C, N, N) float32 adjacency masks per latency class.
      deg: (1, N) float32 step-invariant per-node degree Σ_{c,j} A[c,·,j].
      lamsum: per-node λeff fold Σ_{c,j} λeff[c,·,j] — (N,)/(1, N) shared
        or (B, N) per-draw (scenario segments, per-draw link params).
      lat_frames: per-class physical latency in frames — (C,) shared or
        (B, C) per-draw (cable-length distributions).
      kp, beta_off: traced controller gains — a scalar or a length-B
        per-draw vector (the batched gain-sweep axis); never compile keys.
      dt_frames: static integration constant.
      num_records: telemetry records to emit (grid length).
      record_every: control periods fused per record (in-kernel loop).
      ctrl_mask: optional (N,) shared or (B, N) per-draw controller-enable
        mask — nodes with mask 0 hold their previous ν (clock holdover).
        Traced; None = all on.
      record_beta: also decimate the per-node net occupancy (frames) to
        every record — a fourth output, computed in-kernel from the
        post-update state against the resident adjacency.  Compile-time
        switch; the ν-only fast path is unchanged when off.
      record_watermarks: carry O(B·N) excursion watermarks in-kernel —
        per-node max |β|, its record index, and the ν min/max — updated
        at every record point from the SAME β aggregation and emitted
        once at the end, so peak excursions are available with no
        (R, B, N) record.  Compile-time switch, composable with
        ``record_beta``.
      record_guard: run the reframing guard decision IN-KERNEL with chunk
        early-exit.  The measure pass (shared with ``record_beta`` /
        ``record_watermarks``) compares each node's net occupancy against
        the traced degree-scaled band [``guard_lo``·deg, ``guard_hi``·deg]
        and records the first violating record index per draw in a (B, 1)
        int32 trip output (sentinel ``num_records`` = never tripped).
        Once ANY draw trips, every later record freezes (predicated
        no-ops): state, ν/β records and watermarks stop at the trip
        record, so the host observes the trip after ONE record period and
        resumes from the frozen state — no host-side β scan per chunk.
        Compile-time switch; the guard-off path is byte-identical.
      guard_lo, guard_hi: traced guard band in frames per unit weighted
        degree — scalar or per-draw length-B (target ∓ margin-derived
        threshold).  Required with ``record_guard``.
      guard_stop: traced last record index to execute (scalar or per-draw
        int32; same value across draws).  Records after ``guard_stop``
        are no-ops even without a trip — the host uses this to run a
        PARTIAL chunk on the same compiled kernel (zero-recompile splice
        resumes).  Required with ``record_guard``.
      interpret: run in interpret mode (CPU validation).
      pulses: the FINC/FDEC controller (:class:`repro.kernels.period.
        Pulses`, static fs and pulse budget) in place of the proportional
        one; ``c_est`` is then its (B, N) accumulated correction at the
        start, carried in VMEM across the whole run and returned.
        Compile-time switch.
      edges: integer readout (:class:`Incidence`): every period each
        edge's occupancy is formed from one-hot gathers of the state,
        rounded to a whole frame and summed by destination on the MXU;
        ``lam_edges`` is its (E,)/(1, E) shared or (B, E) per-draw λeff.
        The β record, the watermarks and the guard read the unrounded
        occupancy as before.  Compile-time switch.

    Returns:
      :class:`repro.kernels.EngineOutputs` — (psi_final (B, N), nu_final
      (B, N), freq = nu_rec (num_records, B, N), beta = beta_rec
      (num_records, B, N) or None, watermarks or None, guard_state (B, 1)
      int32 or None, c_est (B, N) or None) where watermarks =
      (beta_abs_max (B, N) f32, peak_record (B, N) i32, nu_min (B, N)
      f32, nu_max (B, N) f32).
    """
    b, n = psi.shape
    c = a.shape[0]
    _check_shapes(b, n, num_records, record_every)
    if (pulses is None) != (c_est is None):
        raise ValueError("pulses and c_est go together")
    if (edges is None) != (lam_edges is None):
        raise ValueError("edges and lam_edges go together")
    e_pad = 0 if edges is None else int(edges.dst.shape[0])
    vmem = fused_vmem_bytes(b, n, c, record_beta=record_beta,
                            record_watermarks=record_watermarks,
                            record_guard=record_guard, edges=e_pad,
                            pulses=pulses is not None)
    if vmem > VMEM_BUDGET_BYTES and not interpret:
        raise ValueError(
            f"fused kernel resident set {vmem/2**20:.1f} MiB exceeds the "
            f"{VMEM_BUDGET_BYTES/2**20:.0f} MiB VMEM budget (B={b}, N={n}, "
            f"C={c}); use bittide_tiled_fused_pallas (adjacency streamed in "
            "column panels) for networks this large")

    law = {}
    if pulses is not None:
        law["pulses"] = period.Pulses(float(pulses.fs), int(pulses.budget))
    if edges is not None:
        law["integer"] = True
    kern = functools.partial(
        _fused_kernel, dt_frames=float(dt_frames),
        record_every=int(record_every), num_classes=int(c), **law)
    in_specs, args = _dense_inputs(
        psi, nu, nu_u, a, deg, lamsum, lat_frames, kp, beta_off, ctrl_mask,
        (c, n, n), lambda t: (0, 0, 0))
    scratch = [pltpu.VMEM((b, n), jnp.float32),           # ψ carry
               pltpu.VMEM((b, n), jnp.float32)]           # ν carry
    if edges is not None:
        lam_e = jnp.asarray(lam_edges, jnp.float32)
        lam_e = lam_e.reshape(-1, lam_e.shape[-1])
        if lam_e.shape not in ((1, e_pad), (b, e_pad)):
            raise ValueError(f"lam_edges must be ({e_pad},), (1, {e_pad}) "
                             f"or ({b}, {e_pad}), got {lam_e.shape}")
        ops = [jnp.asarray(edges.src, jnp.bfloat16),
               jnp.asarray(edges.dst, jnp.bfloat16),
               jnp.asarray(edges.scat, jnp.float32), lam_e]
        in_specs += [pl.BlockSpec(x.shape, _whole_map(x.ndim)) for x in ops]
        args += ops
    if pulses is not None:
        in_specs.append(pl.BlockSpec((b, n), period.whole))
        args.append(jnp.asarray(c_est, jnp.float32))
        scratch.append(pltpu.VMEM((b, n), jnp.float32))   # c_est carry
    return period.launch(
        kern, name="bittide_fused", grid=(num_records,), in_specs=in_specs,
        args=args, scratch=scratch,
        b=b, n=n, num_records=num_records, record_beta=record_beta,
        record_watermarks=record_watermarks, record_guard=record_guard,
        guard_lo=guard_lo, guard_hi=guard_hi, guard_stop=guard_stop,
        interpret=interpret, pulses=pulses is not None)


def _dense_inputs(psi, nu, nu_u, a, deg, lamsum, lat_frames, kp, beta_off,
                  ctrl_mask, a_block, a_map):
    """In-specs and args of the dense lanes' fixed inputs: the adjacency
    in ``a_block`` blocks at ``a_map``, everything else whole."""
    b, n = psi.shape
    c = a.shape[0]
    mask = _mask_row(ctrl_mask, n, b)
    in_specs = [
        pl.BlockSpec((b, c), period.whole),               # lat per draw
        pl.BlockSpec(a_block, a_map),                     # A
        pl.BlockSpec((b, n), period.whole),               # psi0
        pl.BlockSpec((b, n), period.whole),               # nu0
        pl.BlockSpec((b, n), period.whole),               # nu_u
        pl.BlockSpec((b, 1), period.whole),               # kp per draw
        pl.BlockSpec((b, 1), period.whole),               # beta_off
        pl.BlockSpec((mask.shape[0], n), period.whole),   # ctrl mask
        pl.BlockSpec((1, n), period.whole),               # deg
        pl.BlockSpec((b, n), period.whole),               # lamsum per draw
    ]
    args = [_lat_rows(lat_frames, b, c), a.astype(jnp.float32),
            psi.astype(jnp.float32), nu.astype(jnp.float32),
            nu_u.astype(jnp.float32), _gain_col(kp, b, "kp"),
            _gain_col(beta_off, b, "beta_off"), mask,
            deg.reshape(1, n).astype(jnp.float32),
            _lamsum_rows(lamsum, b, n)]
    return in_specs, args


def _tiled_kernel(lat_ref, a_ref, psi0_ref, nu0_ref, nu_u_ref, kp_ref,
                  boff_ref, mask_ref, deg_ref, lamsum_ref, *rest,
                  dt_frames: float, tile_j: int, num_classes: int,
                  record_beta: bool, record_watermarks: bool,
                  record_guard: bool):
    t = pl.program_id(0)
    p = pl.program_id(1)
    j = pl.program_id(2)
    j_tiles = pl.num_programs(2)
    measure = record_beta or record_watermarks or record_guard
    periods = period.measure_periods(measure)
    r = period.unpack(rest, record_beta, record_watermarks, record_guard)
    psi_s, nu_s, acc_s = r.scratch
    period.seed(r, psi0_ref, nu0_ref,
                jnp.logical_and(t == 0, jnp.logical_and(p == 0, j == 0)))

    def _step():
        # Partial aggregation over this j panel: columns [j·TJ, (j+1)·TJ).
        # a_ref is the streamed (C, N, TILE_J) panel; the state stays
        # whole in scratch and only its matching column slice feeds the
        # contraction.
        cols = pl.ds(pl.multiple_of(j * tile_j, TILE), tile_j)
        psi_j = psi_s[:, cols]                                # (B, TJ)
        nu_j = nu_s[:, cols]
        lat = lat_ref[...]                                    # (B, C)
        if measure:
            m = period.row_mean(psi_s)                        # (B, 1)
            psi_j = period.centre(psi_j, m, p == periods)
        partial = jnp.zeros(psi_s.shape, jnp.float32)
        for c in range(num_classes):
            x = psi_j - nu_j * lat[:, c:c + 1]
            # err[b, i] += Σ_{j∈panel} A[c, i, j] · x[b, j]
            partial = partial + jax.lax.dot_general(
                x, a_ref[c],
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=_F32_MXU,
                preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _init_acc():
            acc_s[...] = partial

        @pl.when(j > 0)
        def _accum():
            acc_s[...] += partial

        # Last panel of the period: fold invariants, apply controller,
        # step.
        @pl.when(jnp.logical_and(j == j_tiles - 1, p < periods))
        def _finalize():
            psi_next, nu_next = period.control(
                acc_s, psi_s[...], nu_s[...], nu_u_ref[...], kp_ref,
                boff_ref, deg_ref, lamsum_ref, mask_ref, dt_frames)
            psi_s[...] = psi_next
            nu_s[...] = nu_next
            # Telemetry flushes to HBM when the record index t advances,
            # so overwriting every period within a record is decimation
            # for free.
            r.rec[...] = nu_next[None]
            r.psi_out[...] = psi_next
            r.nu_out[...] = nu_next

        if measure:
            # Last panel of the measure pass: the accumulator now holds
            # the full aggregation of the record's post-update state.
            @pl.when(jnp.logical_and(j == j_tiles - 1, p == periods))
            def _record_beta():
                bnode = (acc_s[...]
                         - (psi_s[...] - m) * deg_ref[...]
                         + lamsum_ref[...])
                period.measure(r, bnode, nu_s, t, deg_ref)

    period.run(r, t, _step)


def bittide_tiled_fused_pallas(psi, nu, nu_u, a, deg, lamsum, lat_frames,
                               kp, beta_off, dt_frames: float,
                               *, num_records: int, record_every: int,
                               tile_j: int, ctrl_mask=None,
                               record_beta: bool = False,
                               record_watermarks: bool = False,
                               record_guard: bool = False, guard_lo=None,
                               guard_hi=None, guard_stop=None,
                               interpret: bool = False):
    """Tiled fused engine: adjacency streamed in (C, N, tile_j) panels.

    Same contract as :func:`bittide_fused_pallas`, but the grid is
    ``(num_records, record_every, N // tile_j)`` and the adjacency block
    spec walks the j panels, so VMEM holds one double-buffered panel
    instead of the whole (C, N, N) stack — Fig-18-scale networks run in
    one ``pallas_call`` without the per-step fallback.  ``tile_j`` must be
    a multiple of TILE dividing N (use :func:`select_engine` to pick it).

    With ``record_beta`` (or ``record_watermarks``) the period grid axis
    grows by ONE extra pass per record —
    ``(num_records, record_every + 1, N // tile_j)`` — that re-streams
    the panels to aggregate the post-update state's per-node net
    occupancy (the state advances only on the first ``record_every``
    passes).  Streaming overhead is therefore (record_every+1)/record_every;
    the flags are compile-time switches and the ν-only grid is unchanged
    when both are off.  Watermarks share the extra pass with β recording
    when both are on, so the combination costs no additional streaming.
    ``record_guard`` (with traced ``guard_lo`` / ``guard_hi`` /
    ``guard_stop``) shares the same measure pass and adds the (B, 1)
    int32 trip output with chunk early-exit — see
    :func:`bittide_fused_pallas`.
    """
    b, n = psi.shape
    c = a.shape[0]
    _check_shapes(b, n, num_records, record_every)
    if tile_j < TILE or tile_j % TILE or n % tile_j:
        raise ValueError(
            f"tile_j={tile_j} must be a multiple of {TILE} dividing N={n}")
    j_tiles = n // tile_j
    vmem = tiled_vmem_bytes(b, n, c, tile_j, record_beta=record_beta,
                            record_watermarks=record_watermarks,
                            record_guard=record_guard)
    if vmem > VMEM_BUDGET_BYTES and not interpret:
        raise ValueError(
            f"tiled working set {vmem/2**20:.1f} MiB exceeds the "
            f"{VMEM_BUDGET_BYTES/2**20:.0f} MiB VMEM budget (B={b}, N={n}, "
            f"C={c}, tile_j={tile_j}); shrink tile_j or use the segment-sum "
            "simulator in repro.core.frame_model")

    kern = functools.partial(
        _tiled_kernel, dt_frames=float(dt_frames), tile_j=int(tile_j),
        num_classes=int(c))
    # A column panel: the index map advances with j, so the Pallas
    # pipeline double-buffers the HBM fetch of panel j+1 behind the
    # matmul on panel j.
    in_specs, args = _dense_inputs(
        psi, nu, nu_u, a, deg, lamsum, lat_frames, kp, beta_off, ctrl_mask,
        (c, n, tile_j), lambda t, p, j: (0, 0, j))
    measure = record_beta or record_watermarks or record_guard
    return period.launch(
        kern, name="bittide_tiled",
        grid=(num_records, record_every + (1 if measure else 0), j_tiles),
        in_specs=in_specs, args=args,
        scratch=[pltpu.VMEM((b, n), jnp.float32),         # ψ carry
                 pltpu.VMEM((b, n), jnp.float32),         # ν carry
                 pltpu.VMEM((b, n), jnp.float32)],        # err accumulator
        b=b, n=n, num_records=num_records, record_beta=record_beta,
        record_watermarks=record_watermarks, record_guard=record_guard,
        guard_lo=guard_lo, guard_hi=guard_hi, guard_stop=guard_stop,
        interpret=interpret)
