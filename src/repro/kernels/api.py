"""The typed engine call surface: options in, named outputs out.

PR 10's API consolidation: engine selection knobs live in the frozen
:class:`EngineOptions` (accepted as ``options=`` by ``simulate_fused``,
``simulate_ensemble_dense``, ``run_scenario``, ``ChaosCampaign.run``,
and ``BittideNetwork.run_scenario``), and the raw engine lanes return a
named :class:`EngineOutputs` instead of the positional 5-tuple that had
to be reshuffled every time a telemetry axis was added.  The old kwargs
(``engine=``, ``chunk_records=``) keep working, mapped silently.
Whether the kernels run in the Pallas interpreter is no option: the
backend decides it (``repro.kernels.ops._auto_interpret``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

__all__ = ["EngineOptions", "EngineOutputs", "resolve_options"]


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """How to run an engine (everything that is not *what to observe*).

    Attributes:
      engine: lane name — "auto" dispatches by shape/degree; explicit
        values are "fused" / "tiled" / "sparse" / "per-step" (and
        "segment-sum" where the scenario runner accepts it).
      chunk_records: records per kernel launch in the scenario runner
        (None = the runner's default).  With the in-kernel guard this
        is a latency/launch-overhead trade only — a guard trip freezes
        the chunk at the trip record, so exposure no longer grows with
        the chunk length.
    """

    engine: str = "auto"
    chunk_records: Optional[int] = None


class EngineOutputs(NamedTuple):
    """Named engine-lane outputs (replaces the positional 5-tuple).

    ``freq`` is the decimated ν record stream; ``psi`` / ``nu`` the
    final carried state; ``beta`` / ``watermarks`` are ``None`` unless
    requested; ``guard_state`` is the (B, 1) int32 first-trip record
    index (sentinel ``num_records`` = never tripped), ``None`` when the
    in-kernel guard is off; ``c_est`` is the FINC/FDEC controller's
    (B, N) accumulated correction after the last period, ``None`` under
    the proportional controller.
    """

    psi: Any
    nu: Any
    freq: Any
    beta: Optional[Any] = None
    watermarks: Optional[tuple] = None
    guard_state: Optional[Any] = None
    c_est: Optional[Any] = None


def resolve_options(options: Optional[EngineOptions], caller: str, *,
                    engine=None, chunk_records=None,
                    default_engine: str = "auto") -> EngineOptions:
    """Merge legacy kwargs into an :class:`EngineOptions`.

    Legacy values are ``None`` when not passed; a passed value wins over
    the ``options`` field.  ``engine=`` / ``chunk_records=`` are mapped
    silently (they are not booleans — the warn set is the boolean sprawl
    the redesign retires).
    """
    base = options if options is not None else EngineOptions(
        engine=default_engine)
    if not isinstance(base, EngineOptions):
        raise TypeError(
            f"{caller}: options= must be a repro.kernels.EngineOptions, "
            f"got {type(options).__name__}")
    updates = {}
    if engine is not None:
        updates["engine"] = engine
    if chunk_records is not None:
        updates["chunk_records"] = chunk_records
    return dataclasses.replace(base, **updates) if updates else base
