#!/usr/bin/env bash
# Per-PR gate: lint + tier-1 tests + cross-engine parity matrix + fast
# benchmark smoke with a JSON perf record compared against the committed
# baseline.
#
#   scripts/ci.sh [--fast] [extra pytest args...]
#
# --fast is the per-push quick gate (see .github/workflows/ci.yml): lint,
# tier-1 tests minus the `slow` marker (heavy parity-matrix / envelope /
# long-horizon suites) and the `model_smoke` marker (the ModelZoo
# per-architecture suite), and the benchmark smoke lane.  The no-flag run
# is the full PR gate.
#
# Writes BENCH_kernels.json at the repo root (the fused/tiled-engine perf
# trajectory; see benchmarks/README.md).
# Exits nonzero if lint or tests
# fail, any smoke bench reports FAIL, or the baseline comparison finds a
# hard gate.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

FAST=0
if [ "${1:-}" = "--fast" ]; then
    FAST=1
    shift
fi

# Lint gate (ruff.toml at the repo root).  The gate is mandatory where
# ruff is installed, and in CI (CI=true, set by GitHub Actions) a missing
# ruff is itself a failure — the workflow installs the exact pin from
# requirements-ci.txt, so "not installed" there means the environment is
# broken and the gate must not silently degrade to a warn-and-skip.
# Hermetic local containers without ruff still get the loud skip.
if command -v ruff >/dev/null 2>&1; then
    ruff check src tests benchmarks scripts examples
    echo "ci: lint green (ruff)"
elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check src tests benchmarks scripts examples
    echo "ci: lint green (python -m ruff)"
elif [ -n "${CI:-}" ]; then
    echo "ci: FAIL ruff not installed in CI; the lint gate cannot run" \
         "(requirements-ci.txt pins it — check the install step)" >&2
    exit 1
else
    echo "ci: WARNING ruff not installed; lint gate skipped" >&2
fi

if [ "$FAST" -eq 1 ]; then
    # model_smoke (the ModelZoo per-architecture suite) is full-tier only:
    # it exercises a different subsystem and dominates fast-gate wall time.
    python -m pytest -x -q -m "not slow and not model_smoke" "$@"

    # Chaos smoke lane: a small randomized fault-injection campaign
    # end-to-end (samplers -> one-compile batch -> envelope/overflow
    # triage -> shrink-to-repro) — cheap enough for the per-push tier.
    python examples/chaos_campaign.py --smoke --no-plot > /dev/null
    echo "ci: chaos smoke (chaos_campaign --smoke) green"

    # Sparse-lane smoke: the random-graph property matrix + ELL table
    # unit tests must run even when the caller filtered the main pytest
    # invocation down to a subset (the torus3d(34) scale gate itself runs
    # in the bench smoke below via kernel_sparse_scale's pass_scale field).
    if [ $# -gt 0 ]; then
        python -m pytest -q tests/test_sparse_engine.py
    fi
    echo "ci: sparse smoke (test_sparse_engine) green"

    # Deprecation-shim smoke: the legacy boolean kwargs must keep working
    # for one release and warn EXACTLY once per process — a regression
    # here (silent kwarg drop, or a warning storm) breaks every
    # not-yet-migrated caller.
    python - <<'EOF'
import warnings
import numpy as np
from repro.core import ControllerConfig, SimConfig, fully_connected, make_links
from repro.scenarios import FreqStep, Scenario, run_scenario

topo = fully_connected(4)
links = make_links(topo, cable_m=2.0)
cfg = SimConfig(dt=1e-3, steps=48, record_every=12)
sc = Scenario(events=(FreqStep(t=0.02, nodes=(0,), delta_ppm=1.0),))
ppm = np.zeros(4, np.float32)
with warnings.catch_warnings(record=True) as rec:
    warnings.simplefilter("always")
    r1 = run_scenario(topo, links, ControllerConfig(kp=2e-7), ppm, sc, cfg,
                      engine="fused", record_beta=True)
    run_scenario(topo, links, ControllerConfig(kp=2e-7), ppm, sc, cfg,
                 engine="fused", record_beta=True)
dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
assert r1.beta.size > 0, "legacy record_beta= kwarg stopped working"
assert len(dep) == 1, f"expected exactly 1 DeprecationWarning, got {len(dep)}"
assert "record_beta" in str(dep[0].message)
EOF
    echo "ci: deprecation-shim smoke (legacy kwargs work, warn once) green"

    # Flight-recorder smoke: trace a tiny run_scenario in-process, export
    # JSONL, render the report, and hard-fail on any traced-run compile —
    # the whole observability path (record -> export -> render) end to end.
    python scripts/trace_report.py --selftest > /dev/null
    echo "ci: trace smoke (trace_report --selftest) green"

    # Serving smoke lane: one paced ensemble (controlled + free draws)
    # drives the continuous-batching engine under all three disciplines;
    # the driver exits nonzero if bittide goodput falls below barrier.
    python examples/serve_bittide.py --smoke --no-plot > /dev/null
    echo "ci: serving smoke (serve_bittide --smoke) green"
else
    python -m pytest -x -q "$@"

    # The cross-engine parity matrix + dispatch/gain-sweep/scenario/
    # reframing gates must run even when the caller filtered the main
    # pytest invocation down to a subset; a no-argument run already
    # covered them above, so don't pay for them twice.
    if [ $# -gt 0 ]; then
        python -m pytest -q tests/test_kernels_fused.py \
            tests/test_engine_dispatch.py tests/test_gain_sweep.py \
            tests/test_scenarios.py tests/test_ensemble_links.py \
            tests/test_beta_telemetry.py tests/test_reframing.py \
            tests/test_chaos.py tests/test_sparse_engine.py
    fi

    # Scenario smoke lanes: the §5.6 fiber-swap demo end-to-end (scenario
    # compiler + runner + Table-2 latency shifts) and the closed-loop
    # re-centering demo (guard band + rotation splices + RTT conservation).
    python examples/cable_swap.py --smoke --no-plot > /dev/null
    python examples/auto_reframe.py --smoke --no-plot > /dev/null
    python examples/chaos_campaign.py --smoke --no-plot > /dev/null
    python examples/serve_bittide.py --smoke --no-plot > /dev/null
    echo "ci: scenario smoke (cable_swap, auto_reframe, chaos_campaign," \
         "serve_bittide --smoke) green"
fi

python -m benchmarks.run --smoke --json BENCH_kernels.json
python scripts/compare_bench.py BENCH_kernels.json \
    benchmarks/baselines/BENCH_kernels.json
if [ "$FAST" -eq 1 ]; then
    echo "ci: fast gate green (lint, not-slow tests, smoke benches)"
else
    echo "ci: tests green, parity matrix green, BENCH_kernels.json written"
fi
