"""Flight recorder: typed, wall-clock-stamped run tracing.

A :class:`RunTrace` accumulates :class:`TraceEvent` records — engine
dispatch decisions, nested spans with timings, reframe guard
evaluations, chaos per-draw verdicts, jit-cache deltas — and byte
counters from `run_scenario` and `ChaosCampaign`.  The recorder is
**host-side only**: spans wrap already-jitted calls with
``time.perf_counter`` stamps, so tracing can never introduce a new
compile (the `no_new_compiles` test pins this).

Every span records its ``id`` and its ``parent``, the span open around
it, so :meth:`RunTrace.totals` sums each kind and
:meth:`RunTrace.self_times` subtracts what child spans cover.

Event taxonomy (the `kind` field; ``run_scenario`` emits all but the
last three):

    scenario          span: one run_scenario call, parent of the rest
    segment.compile   span: compile_scenario (when not passed compiled=)
    segment.stacks    span: the segment plan's lookup, and the dense
                      adjacency stacks (scattered on the device) / sparse
                      slot tables when it builds them
    segment.upload    span: their device_put — the dense stacks' edge
                      lists (child of segment.stacks)
    segment.prep      span: a segment's prep at its start (ppm and λeff
                      folds, λ table, padding, uploads, initial state)
    segment.splice    span: a re-establish with its live-state read
    guard             span: guard margins, a Laplacian pseudo-inverse,
                      the in-kernel guard band
    reframe           span: one pointer rotation, explicit or automatic,
                      with the re-prep that follows it
    chunk             span: one compiled chunk launch inside a segment,
                      until its results are on the host; on the kernel
                      lanes its children are
    chunk.dispatch    span: the engine call until it returns
    chunk.wait        span: block_until_ready on the engine's outputs
    chunk.fetch       span: device-to-host copies, slicing, watermarks
    engine_dispatch   engine lane picked + select_engine regime/VMEM est
    guard_eval        reframe guard decision at a chunk boundary
    compile_stats     jit-cache sizes snapshot (see compile_stats.py)
    segment           span: a campaign phase (ChaosCampaign)
    chaos_draw        one campaign draw's triage verdict
    mark              freeform user annotation

Counters (:meth:`RunTrace.count`): ``h2d_bytes``, the padded bytes of
every host array the run places on the device; ``d2h_bytes``, the bytes
of every device array it reads back (the kernel lanes' records and
state cut to their unpadded draws and nodes on the device first);
``d2h_reads``, the number of those blocking reads; ``prep_plan_builds``
or ``prep_plan_hits``, one of the two a kernel-lane call, as the call
built its fabric's segment plan or reused its stacks or slot tables
(``repro.scenarios.plan``).

Export is JSON-lines (a header line with the counters first, then one
event per line) and round-trips through :meth:`RunTrace.from_jsonl`.
With ``RunTrace(annotate=True)`` each span also opens a
``jax.profiler.TraceAnnotation``, so spans show up in a profiler
capture; :meth:`RunTrace.profiler_ns` places a span's start on that
capture's clock.  The process keeps the newest ``PROFILED_MAX`` such
recorders (:func:`profiled_traces`), so whoever reads a capture after
the traced calls have returned finds their totals and counters too.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import json
import time
from typing import Any, Deque, Dict, Iterable, List, Optional, Tuple

import numpy as np

__all__ = ["TraceEvent", "RunTrace", "NULL_TRACE", "coerce_trace",
           "profiled_traces"]

_SCHEMA = "bittide-run-trace/1"

# A 30 s testbed benchmark window makes about 1,000 traced calls of ~22
# events each; 2,048 recorders of ~12 KB hold a whole window.
PROFILED_MAX = 2048
_PROFILED: Deque["RunTrace"] = collections.deque(maxlen=PROFILED_MAX)


def profiled_traces() -> List["RunTrace"]:
    """The newest ``RunTrace(annotate=True)`` recorders made in this
    process, oldest first (at most ``PROFILED_MAX``)."""
    return list(_PROFILED)


def _jsonable(v: Any) -> Any:
    """Coerce numpy / jax scalars and small arrays to JSON-safe values."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if hasattr(v, "tolist"):  # ndarray / jax.Array
        arr = np.asarray(v)
        if arr.size > 64:  # traces are summaries, not records
            return {"shape": list(arr.shape), "dtype": str(arr.dtype)}
        return arr.tolist()
    return repr(v)


@functools.lru_cache(maxsize=None)
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported once (None when the
    profiler is unavailable: spans are then plain spans)."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001
        return None
    return TraceAnnotation


def annotation_name(kind: str, data: Dict[str, Any]) -> str:
    """The profiler annotation a span opens: ``kind:label`` with the
    label its ``name`` (else its ``engine``) field, else ``kind``."""
    label = data.get("name", data.get("engine", ""))
    return f"{kind}:{label}" if label else kind


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One record: instant event (``dur is None``) or completed span."""

    kind: str
    t: float                      # seconds since the trace epoch
    dur: Optional[float] = None   # span duration in seconds, None if instant
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)
    id: Optional[int] = None      # a span's id, unique within its trace
    parent: Optional[int] = None  # id of the span open around this record

    def to_json(self) -> str:
        row = {"kind": self.kind, "t": round(self.t, 6)}
        if self.dur is not None:
            row["dur"] = round(self.dur, 6)
        if self.data:
            row["data"] = _jsonable(self.data)
        if self.id is not None:
            row["id"] = self.id
        if self.parent is not None:
            row["parent"] = self.parent
        return json.dumps(row, sort_keys=True)

    @classmethod
    def from_json(cls, line: str) -> "TraceEvent":
        row = json.loads(line)
        return cls(kind=row["kind"], t=row["t"], dur=row.get("dur"),
                   data=row.get("data", {}), id=row.get("id"),
                   parent=row.get("parent"))


class RunTrace:
    """Accumulates trace events and counters against one epoch."""

    def __init__(self, name: str = "run", annotate: bool = False,
                 epoch: Optional[float] = None):
        self.name = name
        self.annotate = annotate
        self.epoch = time.time() if epoch is None else epoch
        # The profiler stamps host events on the wall clock
        # (CLOCK_REALTIME; a capture's "Task Environment" plane gives its
        # profile_start_time on it): read beside perf_counter, it maps a
        # span's ``t`` onto the capture (see profiler_ns).
        self.clock_ns = time.time_ns()
        self._t0 = time.perf_counter()
        self.events: List[TraceEvent] = []
        self.counters: Dict[str, int] = {}
        self._open: List[Tuple[int, dict]] = []   # (id, data), innermost last
        self._next_id = 0
        if annotate:
            _PROFILED.append(self)

    # ------------------------------------------------------------ recording

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _parent(self) -> Optional[int]:
        return self._open[-1][0] if self._open else None

    def event(self, kind: str, **data: Any) -> TraceEvent:
        ev = TraceEvent(kind=kind, t=self._now(), data=data,
                        parent=self._parent())
        self.events.append(ev)
        return ev

    @contextlib.contextmanager
    def span(self, kind: str, **data: Any):
        """Record a timed span under the span open around it; mirrored to
        jax.profiler when ``annotate``."""
        sid, parent = self._next_id, self._parent()
        self._next_id += 1
        ann = _trace_annotation() if self.annotate else None
        if ann is not None:
            ann = ann(annotation_name(kind, data))
        self._open.append((sid, data))
        start = self._now()
        try:
            if ann is None:
                yield self
            else:
                with ann:
                    yield self
        finally:
            dur = self._now() - start
            self._open.pop()
            self.events.append(TraceEvent(kind=kind, t=start, dur=dur,
                                          data=data, id=sid, parent=parent))

    def note(self, **data: Any) -> None:
        """Add fields to the innermost open span (known only inside it)."""
        if self._open:
            self._open[-1][1].update(data)

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to the counter ``name``."""
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def profiler_ns(self, t: float) -> int:
        """A trace time ``t`` on the profiler's clock, in ns."""
        return self.clock_ns + int(round(t * 1e9))

    # ------------------------------------------------------------- querying

    def __len__(self) -> int:
        return len(self.events)

    def __bool__(self) -> bool:
        # An EMPTY recorder is still a live recorder — never let __len__
        # drive `if trace:` instrumentation gates.
        return True

    def by_kind(self, kind: str) -> List[TraceEvent]:
        return [e for e in self.events if e.kind == kind]

    def _spans(self) -> Iterable[TraceEvent]:
        return (e for e in self.events if e.dur is not None)

    def totals(self) -> Dict[str, float]:
        """{kind: summed span duration in seconds}."""
        out: Dict[str, float] = {}
        for e in self._spans():
            out[e.kind] = out.get(e.kind, 0.0) + e.dur
        return out

    def self_times(self) -> Dict[str, float]:
        """{kind: summed span duration minus what its child spans cover}."""
        covered: Dict[int, float] = {}
        for e in self._spans():
            if e.parent is not None:
                covered[e.parent] = covered.get(e.parent, 0.0) + e.dur
        out: Dict[str, float] = {}
        for e in self._spans():
            own = e.dur - (covered.get(e.id, 0.0) if e.id is not None else 0.0)
            out[e.kind] = out.get(e.kind, 0.0) + own
        return out

    def summary(self) -> str:
        """Per-kind table: count, total span time, worst span; counters."""
        kinds: Dict[str, List[TraceEvent]] = {}
        for e in self.events:
            kinds.setdefault(e.kind, []).append(e)
        lines = [f"RunTrace '{self.name}': {len(self.events)} events",
                 f"{'kind':<16} {'count':>5} {'total_ms':>9} {'max_ms':>8}"]
        for kind in sorted(kinds):
            evs = kinds[kind]
            durs = [e.dur for e in evs if e.dur is not None]
            tot = f"{sum(durs) * 1e3:9.1f}" if durs else f"{'-':>9}"
            mx = f"{max(durs) * 1e3:8.1f}" if durs else f"{'-':>8}"
            lines.append(f"{kind:<16} {len(evs):>5} {tot} {mx}")
        for name in sorted(self.counters):
            lines.append(f"counter {name}: {self.counters[name]}")
        return "\n".join(lines)

    # -------------------------------------------------------------- JSONL IO

    def to_jsonl(self, path: str) -> None:
        with open(path, "w") as fh:
            fh.write(json.dumps({"schema": _SCHEMA, "name": self.name,
                                 "epoch": self.epoch,
                                 "clock_ns": self.clock_ns,
                                 "counters": self.counters}) + "\n")
            for ev in self.events:
                fh.write(ev.to_json() + "\n")

    @classmethod
    def from_jsonl(cls, path: str) -> "RunTrace":
        with open(path) as fh:
            lines = [ln for ln in (l.strip() for l in fh) if ln]
        if not lines:
            raise ValueError(f"{path}: empty trace file")
        head = json.loads(lines[0])
        if head.get("schema") != _SCHEMA:
            raise ValueError(f"{path}: not a {_SCHEMA} file "
                             f"(schema={head.get('schema')!r})")
        tr = cls(name=head.get("name", "run"), epoch=head.get("epoch"))
        tr.clock_ns = head.get("clock_ns")
        tr.counters = dict(head.get("counters", {}))
        tr.events = [TraceEvent.from_json(ln) for ln in lines[1:]]
        return tr


_NULL_SPAN = contextlib.nullcontext()


class _NullTrace:
    """No-op stand-in so instrumented code needs no `if trace:` litter."""

    annotate = False
    events: List[TraceEvent] = []

    def event(self, kind: str, **data: Any) -> None:
        return None

    def span(self, kind: str, **data: Any):
        return _NULL_SPAN

    def note(self, **data: Any) -> None:
        return None

    def count(self, name: str, n: int) -> None:
        return None

    def __bool__(self) -> bool:
        return False


NULL_TRACE = _NullTrace()


def coerce_trace(trace: Any, name: str = "run") -> Any:
    """Normalize a `trace=` argument: False->no-op, True->fresh RunTrace,
    an existing RunTrace passes through (shared across layers)."""
    if isinstance(trace, RunTrace):
        return trace
    if trace:
        return RunTrace(name=name)
    return NULL_TRACE
