"""One-release deprecation machinery for the typed options/telemetry API.

PR 10 replaced the sprawl of boolean engine kwargs (``record_beta``,
``record_watermarks``, ``trace``, ``auto_reframe``) with the frozen
:class:`repro.kernels.EngineOptions` /
:class:`repro.telemetry.Telemetry` objects.  The old kwargs keep working
for one release; each emits exactly ONE :class:`DeprecationWarning` per
process (keyed on the kwarg name) and is mapped onto the new object.

This module has no dependencies so both ``repro.kernels`` and
``repro.telemetry`` can import it without cycles.
"""
from __future__ import annotations

import warnings

_WARNED: set = set()


def deprecated_kwarg(old: str, new: str, *, stacklevel: int = 4) -> None:
    """Warn ONCE per process that ``old`` should become ``new``."""
    if old in _WARNED:
        return
    _WARNED.add(old)
    warnings.warn(
        f"{old} is deprecated and will be removed after one release; "
        f"use {new}", DeprecationWarning, stacklevel=stacklevel)


def reset_deprecation_warnings() -> None:
    """Re-arm the warn-once registry (test helper)."""
    _WARNED.clear()
