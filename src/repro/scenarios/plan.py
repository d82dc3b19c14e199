"""The segment plan: what ``run_scenario``'s kernel lanes derive from a
fabric without its draws, kept for the next call on an equal fabric.

A call on a fabric — topology, links, ``SimConfig`` and events (or the
``compiled=`` scenario) — builds the dense adjacency stacks or sparse
slot tables of every segment and keeps them, with the fabric's own β0,
in a :class:`SegmentPlan`.  The segments' padded device inputs (engine
choice, latency-class rows, mask, kp / β_off columns, the fold of β0)
depend on the controller, the draw count, the lane and the telemetry
variant too; the plan holds them for one such ``batch`` key and makes
them again when the key changes, keeping the stacks.  So a call that
repeats the last one with new draws does only the work the draws
change, a gain sweep or a telemetry change on one fabric keeps the
stacks, and a call on another fabric builds everything, as a call with
no plan does.

Only one plan is kept.  A call on another fabric drops it before it
builds, so the device holds at most one fabric's stacks, as during a
call with no plan; between calls the plan holds them (one segment's
stack is C·N_pad²·4 bytes: 462 MB for the 10,648-node torus).

The keys are content, never object identity: :func:`snapshot` copies
every array of the inputs, and :func:`matches` compares a live input
with the copy bit for bit (dtype, shape and bytes, so ``-0.0`` and
``0.0`` differ), which is cheaper than hashing the arrays.  An input
changed in place, or a new object with other values, misses and
rebuilds; an input the snapshot cannot copy (an object of a type it
does not know) gets a plan no later call can match.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

_PLAN: Optional["SegmentPlan"] = None


class _Unkeyable(Exception):
    """An input :func:`snapshot` cannot copy by value."""


@dataclasses.dataclass
class SegmentPlan:
    """One fabric's draw-independent work, filled in by the call that
    builds it and read by every later call that matches ``key``.

    ``stacks`` / ``tables`` are the dense stacks or sparse slot tables;
    ``lam0`` the fabric's own λeff (its β0).  For the ``batch`` key:
    ``inputs[si]`` segment ``si``'s engine, panel and padded device
    inputs, made by the first call that preps the segment; ``index[rows]``
    the flat fold index of a (rows, E) λeff; ``shared`` what segments
    share, made once: gains, latency-class rows, padded slot tables, each
    distinct mask, and the fold of ``lam0`` per edge-weight vector.
    """

    key: object
    stacks: object = None
    tables: object = None
    lam0: Optional[np.ndarray] = None
    batch: object = None
    inputs: Dict[int, object] = dataclasses.field(default_factory=dict)
    index: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    shared: Dict[object, object] = dataclasses.field(default_factory=dict)


_SCALARS = (bool, int, float, str, type(None))
_FIELDS: Dict[type, tuple] = {}     # dataclass type -> its field names


def _fields(t: type) -> tuple:
    names = _FIELDS.get(t)
    if names is None:
        names = _FIELDS[t] = tuple(f.name for f in dataclasses.fields(t))
    return names


def snapshot(x):
    """A by-value copy of ``x`` that :func:`matches` compares against.

    NumPy arrays are copied; tuples, lists and dataclasses are walked;
    numbers, strings, ``None`` and types are kept as they are (they
    cannot change in place).  Raises :class:`_Unkeyable` on anything
    else."""
    if isinstance(x, np.ndarray):
        if x.dtype.hasobject:
            raise _Unkeyable(x.dtype)
        return np.array(x, copy=True)
    if isinstance(x, (tuple, list)):
        return (type(x),) + tuple(snapshot(v) for v in x)
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return (type(x),) + tuple(snapshot(getattr(x, f))
                                  for f in _fields(type(x)))
    if isinstance(x, _SCALARS + (type, np.generic)):
        return x
    raise _Unkeyable(type(x))


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    """Equal dtype, shape and bytes."""
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def matches(snap, x) -> bool:
    """True when ``x`` equals the :func:`snapshot` ``snap`` by value."""
    t = type(x)
    if isinstance(x, np.ndarray):
        return type(snap) is np.ndarray and same_bits(snap, x)
    if t in _SCALARS or isinstance(x, (type, np.generic)):
        # Floats: 0.0 == -0.0, but the sign reaches the results.
        return (type(snap) is t and snap == x
                and (not isinstance(x, (float, np.floating))
                     or bool(np.signbit(snap) == np.signbit(x))))
    if type(snap) is not tuple or not snap or snap[0] is not t:
        return False
    if isinstance(x, (tuple, list)):
        return (len(snap) == len(x) + 1
                and all(matches(s, v) for s, v in zip(snap[1:], x)))
    names = _fields(t) if dataclasses.is_dataclass(x) else ()
    return (len(names) + 1 == len(snap) > 1
            and all(matches(s, getattr(x, f))
                    for s, f in zip(snap[1:], names)))


def _snapshot_or_none(x):
    """:func:`snapshot`, or ``None`` (which nothing matches)."""
    try:
        return snapshot(x)
    except _Unkeyable:
        return None


def lookup(fabric, batch, tr) -> SegmentPlan:
    """The kept plan when ``fabric`` matches its key, else a new empty one
    that replaces it; counts ``prep_plan_hits`` or ``prep_plan_builds`` on
    ``tr``.  A plan whose ``batch`` key does not match ``batch`` forgets
    its segments' inputs."""
    global _PLAN
    plan = _PLAN
    if plan is not None and matches(plan.key, fabric):
        tr.count("prep_plan_hits", 1)
    else:
        _PLAN = None        # the old plan's device buffers go first
        tr.count("prep_plan_builds", 1)
        plan = SegmentPlan(key=_snapshot_or_none(fabric))
        if plan.key is not None:
            _PLAN = plan
    if not matches(plan.batch, batch):
        plan.batch = _snapshot_or_none(batch)
        plan.inputs, plan.index, plan.shared = {}, {}, {}
    return plan


def _clear_plans() -> None:
    """Forget the kept plan (tests start from a cold process with it)."""
    global _PLAN
    _PLAN = None
