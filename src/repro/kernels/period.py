"""One control period of the Pallas lanes, shared by every kernel.

The fused, tiled and sparse kernels (``bittide_step``,
``bittide_sparse``) differ only in how they aggregate the neighbours'
state: the fused kernel loops over its VMEM-resident adjacency in an
in-kernel ``fori_loop``, the tiled kernel accumulates streamed column
panels, the sparse kernel gathers slot-major ELL rows through its
node-major mirror.  Everything else about a period is one decision,
held here once:

- :func:`unpack` names a kernel's trailing refs from its ``record_*``
  flags (the positional order every wrapper builds with
  :func:`launch` and every caller reads back through
  :func:`split_outputs`);
- :func:`seed` loads the initial state and the guard's trip sentinel;
- :func:`control` is the controller law and integrator (the per-step
  kernel calls it too, with scalar gains), with two compile-time
  variants: the FINC/FDEC law (:class:`Pulses`, which carries ``c_est``)
  and integer readout (:func:`read_edges`, each edge's occupancy
  rounded to a whole frame before the node sum);
- :func:`measure` turns one record's per-node net occupancy into the β
  record, the watermarks and the guard trip; :func:`measure_periods`,
  :func:`row_mean` and :func:`centre` are the measure-pass rule of the
  lanes that stream their adjacency;
- :func:`run` is the guard's chunk early exit around a lane's step
  (:func:`live`, from the :func:`never_tripped` sentinel);
- :func:`launch` is the wrapper side: the guard in-specs, the outputs in
  :func:`split_outputs`' order, and the ``pallas_call`` itself.

The module is internal to ``repro.kernels``: the lanes' wrappers are
the interface.  The body reads a ref where it uses it (:func:`_ld`), so a lane may pass
a ref, a loaded array or a static gain for any operand: the fused
kernel passes arrays it loaded once before its period loop, the
streaming kernels pass the refs themselves.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .api import EngineOutputs

# Scoped-VMEM limit every pallas_call compiles against.  Mosaic's default
# scoped limit (16 MiB on v5e) is a compiler setting, not the size of the
# core's VMEM (128 MiB on v5e/v6e): the telemetry variants of the tiled
# lane at Fig-18 scale need more than the default, so every kernel passes
# this limit explicitly.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _is_ref(x) -> bool:
    return isinstance(jax.typeof(x), jax.ref.AbstractRef)


def _ld(x, ix=...):
    """``x``'s value: a ref is read here, at its use; an array or a
    static gain passes through."""
    return x[ix] if _is_ref(x) else x


def _st(x, ix, v):
    """Store ``v`` into ref ``x`` at ``ix`` and return the ref, or
    return ``v`` in place of an array."""
    if not _is_ref(x):
        return v
    x[ix] = v
    return x


class Pulses(NamedTuple):
    """The FINC/FDEC controller's static constants (arXiv:2503.05033
    §4.3): the pulse step ``fs`` (relative frequency) and the most
    pulses a node may issue in one control period.  A compile key."""
    fs: float
    budget: int


class Refs(NamedTuple):
    """A kernel's refs after its fixed inputs, by name.

    ``pallas_call`` passes inputs, then outputs, then scratch.  The guard
    band and stop cap trail the fixed inputs; the β record, the four
    watermarks, the trip column and the FINC/FDEC ``c_est`` trail the
    fixed outputs, in that order.  Absent ones are None.
    """
    glo: Optional[object]
    ghi: Optional[object]
    stop: Optional[object]
    psi_out: object
    nu_out: object
    rec: object
    brec: Optional[object]
    wm: Optional[tuple]    # (|β| max, its record index, ν min, ν max)
    trip: Optional[object]
    c_out: Optional[object]
    scratch: tuple         # ψ carry, ν carry, then the lane's own


def unpack(rest, record_beta: bool, record_watermarks: bool,
           record_guard: bool, pulses: bool = False) -> Refs:
    """Name the refs that follow a kernel's fixed inputs."""
    rest = list(rest)

    def take(k, on=True):
        if not on:
            return (None,) * k
        got = tuple(rest[:k])
        del rest[:k]
        return got

    glo, ghi, stop = take(3, record_guard)
    psi_out, nu_out, rec = take(3)
    brec, = take(1, record_beta)
    wm = take(4) if record_watermarks else None
    trip, = take(1, record_guard)
    c_out, = take(1, pulses)
    return Refs(glo, ghi, stop, psi_out, nu_out, rec, brec, wm, trip, c_out,
                tuple(rest))


def seed(r: Refs, psi0_ref, nu0_ref, first, c0_ref=None):
    """On the first grid step, load the initial state into the ψ/ν
    carries (and ``c0_ref`` into the FINC/FDEC ``c_est`` carry, the
    scratch after them) and set the guard's "never tripped" sentinel:
    num_records, one past any record."""
    psi_s, nu_s = r.scratch[:2]

    @pl.when(first)
    def _seed():
        psi_s[...] = psi0_ref[...]
        nu_s[...] = nu0_ref[...]
        if c0_ref is not None:
            r.scratch[2][...] = c0_ref[...]
        if r.trip is not None:
            r.trip[...] = never_tripped(r.trip.shape, pl.num_programs(0))


def never_tripped(shape, num_records):
    """The trip record's sentinel: num_records, one past any record."""
    return jnp.full(shape, num_records, jnp.int32)


def read_edges(src_term, lam_e, psi_dst):
    """Integer readout: each edge's occupancy read as a whole frame,

        rint(β_e),  β_e = (ψ_src − ν_src·lat_e) + λeff_e − ψ_dst,

    formed in ``repro.core.frame_model``'s order from the gathered
    source term and destination phase; ``jnp.round`` rounds half to
    even, as ``np.rint``."""
    return jnp.round(src_term + _ld(lam_e) - psi_dst)


def finc_fdec(c_rel, c_est, pulses: Pulses):
    """The FINC/FDEC step toward the proportional correction ``c_rel``:
    ``c_est`` moved by n = clip(rint((c_rel − c_est)/fs), ±budget) whole
    pulses of fs."""
    n = jnp.clip(jnp.round((c_rel - c_est) / pulses.fs),
                 -pulses.budget, pulses.budget)
    return c_est + n * pulses.fs


def control(acc, psi, nu, nu_u, kp, beta_off, deg, lamsum, enabled,
            dt_frames, pulses: Optional[Pulses] = None, c_est=None,
            integer: bool = False):
    """One period of the controller and the integrator.

        err = acc − (ψ + β_off)·deg + lamsum     continuous readout
        err = acc − β_off·deg                    integer readout
        ν'  = ν_u + c + ν_u·c
        ψ'  = ψ + ν'·Δt

    ``acc`` is the aggregation Σ_c [A_c @ (ψ − ν·lat_c)] (or its ELL
    gather); with ``integer`` readout it is Σ_{e→i} w_e·rint(β_e)
    (:func:`read_edges`).  The proportional law takes c = kp·err.  With
    ``pulses`` (FINC/FDEC, :class:`Pulses`) a node moves its clock only
    in whole pulses of fs and carries their sum ``c_est``
    (:func:`finc_fdec`):

        n     = clip(rint((kp·err − c_est)/fs), ±budget)
        c     = c_est + n·fs

    as ``repro.core.controller.controller_step`` takes it.  ``enabled``
    is a bool array or the float controller-enable mask (on above 0.5):
    a node switched off holds its previous ν (clock holdover) instead of
    recomputing it, and its ``c_est``.  Returns (ψ', ν'), and with
    ``pulses`` (ψ', ν', c_est').  Both variants are compile-time
    switches: without them the body is the proportional period with
    continuous readout.
    """
    if integer:
        err = _ld(acc) - _ld(beta_off) * _ld(deg)
    else:
        err = _ld(acc) - (_ld(psi) + _ld(beta_off)) * _ld(deg) + _ld(lamsum)
    c_rel = _ld(kp) * err
    if pulses is not None:
        c_est = _ld(c_est)
        c_rel = finc_fdec(c_rel, c_est, pulses)
    nu_u = _ld(nu_u)
    # (1+ν_u)(1+c) − 1 computed as ν_u + c + ν_u·c: never forms
    # 1 + O(1e-6), which would quantize to float32 eps(1.0) = 1.19e-7.
    nu_next = nu_u + c_rel + nu_u * c_rel
    if jax.typeof(enabled).dtype != jnp.bool_:
        enabled = _ld(enabled) > 0.5
    nu_next = jnp.where(enabled, nu_next, _ld(nu))
    psi_next = _ld(psi) + nu_next * dt_frames
    if pulses is None:
        return psi_next, nu_next
    return psi_next, nu_next, jnp.where(enabled, c_rel, c_est)


def fold_watermarks(wm, babs, nu, t, ix=...):
    """Fold record ``t`` into the running watermarks ``wm`` — |β| max,
    its record index, ν min, ν max — and return them.  Refs are updated
    in place at ``ix``; arrays are returned new.  Strict ``>`` keeps the
    FIRST record attaining the max (np.argmax semantics)."""
    beta, idx, lo, hi = wm
    idx = _st(idx, ix, jnp.where(babs > _ld(beta, ix), t, _ld(idx, ix)))
    beta = _st(beta, ix, jnp.maximum(_ld(beta, ix), babs))
    lo = _st(lo, ix, jnp.minimum(_ld(lo, ix), nu))
    hi = _st(hi, ix, jnp.maximum(_ld(hi, ix), nu))
    return beta, idx, lo, hi


def out_of_band(bnode, lo, hi, deg):
    """The reframing guard's test, per node: net occupancy outside the
    degree-scaled band [lo·deg, hi·deg] (lo/hi = target ∓ guard, frames
    per unit weighted degree).  Strict inequalities keep degree-0
    padding nodes (β ≡ 0) inert."""
    return jnp.logical_or(bnode > _ld(hi) * _ld(deg),
                          bnode < _ld(lo) * _ld(deg))


def measure(r: Refs, bnode, nu, t, deg, cols=None):
    """Record ``t``'s measurement from its per-node net occupancy.

    Writes the β record, seeds (record 0) or folds the watermarks, and
    lands ``t`` in the trip column of every draw with a node out of the
    guard band.  ``cols`` restricts the writes to one node panel of the
    whole-row blocks (the sparse lane); None writes whole rows.
    """
    if r.brec is not None:
        if cols is None:
            r.brec[...] = bnode[None]
        else:
            r.brec[0, :, cols] = bnode
    if r.wm is not None:
        # Whole (B, N) accumulators with constant index maps: they stay in
        # VMEM across the grid and flush once at the end.
        ix = ... if cols is None else (slice(None), cols)
        babs = jnp.abs(bnode)
        nu = _ld(nu)

        @pl.when(t == 0)
        def _wm_seed():
            wm_beta, wm_idx, wm_lo, wm_hi = r.wm
            wm_beta[ix] = babs
            wm_idx[ix] = jnp.zeros_like(babs, jnp.int32)
            wm_lo[ix] = nu
            wm_hi[ix] = nu

        @pl.when(t > 0)
        def _wm_update():
            fold_watermarks(r.wm, babs, nu, t, ix)
    if r.trip is not None:
        # The (B, 1) trip block is shared by every panel of a record.
        row_viol = jnp.any(out_of_band(bnode, r.glo, r.ghi, deg), axis=1,
                           keepdims=True)
        r.trip[...] = jnp.where(row_viol, t, r.trip[...])


def measure_periods(measure: bool):
    """Advancing passes per record on a lane whose grid has a period
    axis (1): with a measurement that axis carries one extra trailing
    pass, p == periods, which re-streams the adjacency to aggregate the
    POST-update state's occupancy."""
    return pl.num_programs(1) - (1 if measure else 0)


def row_mean(psi):
    """ψ's per-draw mean, (B, 1).  β is exactly invariant under a
    uniform ψ shift, so the measure pass centres ψ by it: the partial
    sums then stay O(ψ spread) rather than O(ψ magnitude), which keeps
    the float32 record within 1e-6 frames of the edge-list math.  The
    mean is over the whole row, so every panel and every lane subtracts
    the same constant."""
    return jnp.mean(_ld(psi), axis=1, keepdims=True)


def centre(x, m, on):
    """``x`` less the row mean ``m`` where ``on`` (the measure pass)."""
    return jnp.where(on, x - m, x)


def live(trip, t, stop):
    """Whether record ``t`` runs under the guard: no draw tripped at an
    earlier record and ``t`` is within the host's stop cap.
    ``min(trip) ≥ t`` (sentinel num_records) keeps the trip record itself
    fully processed; state, records and watermarks then freeze there and
    the host resumes from them with no recompile."""
    return jnp.logical_and(jnp.min(_ld(trip)) >= t, t <= _ld(stop, (0, 0)))


def run(r: Refs, t, step):
    """Run a lane's ``step`` for this grid step, or, with the guard on,
    only while the chunk is :func:`live`."""
    if r.trip is None:
        step()
        return
    pl.when(live(r.trip, t, r.stop))(step)


def _gain_col(v, b: int, name: str):
    """Normalize a traced gain (scalar or per-draw vector) to (B, 1)."""
    col = jnp.asarray(v, jnp.float32).reshape(-1)
    if col.shape[0] == 1:
        col = jnp.broadcast_to(col, (b,))
    if col.shape[0] != b:
        raise ValueError(f"{name} must be scalar or length-{b} per-draw, "
                         f"got shape {jnp.shape(v)}")
    return col.reshape(b, 1)


def _guard_cols(guard_lo, guard_hi, guard_stop, b: int):
    """Normalize the traced guard inputs to the (B, 1) columns the
    kernels consume: f32 band edges + i32 stop-after record index."""
    if guard_lo is None or guard_hi is None or guard_stop is None:
        raise ValueError(
            "record_guard=True requires guard_lo, guard_hi and guard_stop")
    stop = jnp.asarray(guard_stop, jnp.int32).reshape(-1)
    if stop.shape[0] == 1:
        stop = jnp.broadcast_to(stop, (b,))
    if stop.shape[0] != b:
        raise ValueError(f"guard_stop must be scalar or length-{b}, "
                         f"got shape {jnp.shape(guard_stop)}")
    return [_gain_col(guard_lo, b, "guard_lo"),
            _gain_col(guard_hi, b, "guard_hi"), stop.reshape(b, 1)]


def whole(*_):
    """Index map of a block that is the whole (·, ·) array."""
    return (0, 0)


def _record(t, *_):
    return (t, 0, 0)


def _outputs(b: int, n: int, num_records: int, record_beta: bool,
             record_watermarks: bool, record_guard: bool,
             pulses: bool = False):
    """Out-specs and out-shapes in :func:`split_outputs`' order.

    Every block is whole-row: the pipeline writes an output block back
    each time its index changes and never reads it in, so a panel-wide
    block revisited on the next pass (or skipped by a guard freeze) would
    flush a stale buffer over the panel's results.  ψ, ν, the watermarks
    and the trip column keep constant index maps (VMEM-resident across
    the grid, flushed once at the end); the ν and β records advance with
    the record index, so each record flushes once.  The FINC/FDEC
    variant (``pulses``) ends with the whole-row ``c_est``.
    """
    f32 = jnp.float32
    row = (pl.BlockSpec((b, n), whole), jax.ShapeDtypeStruct((b, n), f32))
    rec = (pl.BlockSpec((1, b, n), _record),
           jax.ShapeDtypeStruct((num_records, b, n), f32))
    outs = [row, row, rec]
    if record_beta:
        outs.append(rec)
    if record_watermarks:
        # |β| max, its record index, ν min, ν max.
        for dt_ in (f32, jnp.int32, f32, f32):
            outs.append((pl.BlockSpec((b, n), whole),
                         jax.ShapeDtypeStruct((b, n), dt_)))
    if record_guard:
        outs.append((pl.BlockSpec((b, 1), whole),
                     jax.ShapeDtypeStruct((b, 1), jnp.int32)))
    if pulses:
        outs.append(row)
    specs, shapes = zip(*outs)
    return list(specs), list(shapes)


def split_outputs(out, record_beta: bool, record_watermarks: bool,
                  record_guard: bool, pulses: bool = False) -> EngineOutputs:
    """:class:`EngineOutputs` from the flat ``pallas_call`` output list."""
    i = 3
    brec = wm = trip = c_est = None
    if record_beta:
        brec = out[i]
        i += 1
    if record_watermarks:
        wm = tuple(out[i:i + 4])
        i += 4
    if record_guard:
        trip = out[i]
        i += 1
    if pulses:
        c_est = out[i]
    return EngineOutputs(psi=out[0], nu=out[1], freq=out[2], beta=brec,
                         watermarks=wm, guard_state=trip, c_est=c_est)


def launch(kernel, *, name: str, grid, in_specs, args, scratch, b: int,
           n: int, num_records: int, record_beta: bool,
           record_watermarks: bool, record_guard: bool, guard_lo, guard_hi,
           guard_stop, interpret: bool, pulses: bool = False
           ) -> EngineOutputs:
    """A lane's ``pallas_call``: its fixed inputs, then the guard band
    and stop cap (three (B, 1) columns) with ``record_guard``; the
    outputs of :func:`_outputs` (with ``pulses``, the FINC/FDEC
    ``c_est`` last); ``kernel`` given the ``record_*`` flags."""
    flags = dict(record_beta=bool(record_beta),
                 record_watermarks=bool(record_watermarks),
                 record_guard=bool(record_guard))
    if record_guard:
        in_specs = in_specs + [pl.BlockSpec((b, 1), whole)] * 3
        args = args + _guard_cols(guard_lo, guard_hi, guard_stop, b)
    out_specs, out_shape = _outputs(b, n, num_records, **flags,
                                    pulses=pulses)
    out = pl.pallas_call(
        functools.partial(kernel, **flags),
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    return split_outputs(out, **flags, pulses=pulses)
