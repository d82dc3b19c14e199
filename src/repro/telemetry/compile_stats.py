"""Jit-cache introspection: compile counts per engine lane.

Promoted out of ``tests/engine_harness.py`` so the flight recorder,
examples, and CLI tooling can assert the zero-recompile guarantee
outside pytest.  ``tests/engine_harness`` re-exports both names, so
existing test imports are unchanged.

Engine objects are imported lazily inside the functions: this module
must stay importable before (and without) the kernel stack, and
``repro.kernels.ops`` itself imports ``repro.telemetry.watermarks``.
"""
from __future__ import annotations

__all__ = ["compile_stats", "engine_cache_sizes", "no_new_compiles"]


def compile_stats() -> dict:
    """Jit-cache entry counts of every lane, for no-recompile assertions.

    fused and tiled share one jitted wrapper (the engine choice is a
    static argument of ``_fused_engine``), so they share a key here.
    ``dense-stacks`` is the scenario runner's device builder of the
    dense lanes' adjacency stacks, ``read-back`` its device slice of the
    kernel lanes' padded outputs before they are read back.
    """
    from repro.core.frame_model import _jitted_run, _jitted_run_ensemble
    from repro.kernels.ops import (_fused_engine, _perstep_engine,
                                   _sparse_engine)
    from repro.scenarios.runner import _scatter_stack, _unpad
    return {
        "fused/tiled": _fused_engine._cache_size(),
        "per-step": _perstep_engine._cache_size(),
        "sparse": _sparse_engine._cache_size(),
        "segment-sum": _jitted_run()._cache_size(),
        "segment-sum-ensemble": _jitted_run_ensemble()._cache_size(),
        "dense-stacks": _scatter_stack._cache_size(),
        "read-back": _unpad._cache_size(),
    }


# Original (pre-promotion) name, kept as the primary test-facing alias.
engine_cache_sizes = compile_stats


class no_new_compiles:
    """Context manager pinning the compile budget of a block::

        with no_new_compiles():            # zero new executables
            run_scenario(...)              # (warm-cache replay)

        with no_new_compiles(sparse=1):    # exactly-once compile budget
            run_scenario(..., engine="sparse")

    Keys are :func:`compile_stats` keys; unnamed lanes must stay
    exactly flat.
    """

    def __init__(self, **budget: int):
        unknown = set(budget) - set(compile_stats())
        if unknown:
            raise KeyError(f"unknown engine cache keys: {sorted(unknown)}")
        self.budget = budget

    def __enter__(self):
        self.before = compile_stats()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        after = compile_stats()
        for k, n0 in self.before.items():
            allowed = self.budget.get(k, 0)
            grew = after[k] - n0
            assert grew <= allowed, (
                f"{k} compiled {grew} new executable(s), budget {allowed}")
        return False
