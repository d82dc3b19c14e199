"""Cross-engine differential test harness.

Five lanes now have to agree — segment-sum, fused, tiled, per-step, and
the sparse ELL engine — and every PR that adds a lane (or tunes one)
re-proves the same contracts: 1e-6-ppm frequency parity at every record
point, β-telemetry parity in the converged bounded-occupancy regime,
zero recompiles across scenario segments, per-draw chaos batches
matching their single-draw replays, and — since the in-kernel reframing
guard — identical trip records across the kernel lanes with
bit-identical outputs when the guard never trips (``guard_case`` /
``run_guarded``).  This module is the single home for
those contracts, factored out of the per-PR ad-hoc matrices that
``test_kernels_fused.py`` / ``test_beta_telemetry.py`` / ``test_chaos.py``
grew: one topology matrix, one tolerance policy, one segment-sum
reference cache, one compile-count guard, and the random bounded-degree
graph builders the hypothesis property tests draw from (via
``hypcompat`` — composed from scalar strategies so the deterministic
fallback runner replays them too).

Tolerance policy
----------------
* ``FREQ_ATOL_PPM`` — absolute frequency parity at every record point.
  All engines run the same float32 math in different orders; 1e-6 ppm
  (1e-12 relative frequency) is the established cross-engine bar.
* ``BETA_ATOL_FRAMES`` — β parity in converged bounded-occupancy
  regimes (|β| = O(1) frames), where an absolute 1e-6-frame float32
  comparison is meaningful.
* ``BETA_ATOL_CROSS_FRAMES`` / ``BETA_RTOL_CROSS`` — β parity across
  engines in NON-converged or event-driven regimes, where |β| reaches
  O(10²–10³) frames and the comparison floor is set by float32
  resolution at that scale: an absolute floor plus a relative term of
  four float32 ulps (one ulp of a 273-frame β is 3.05e-5 frames, more
  than the absolute floor alone admits).
"""
import numpy as np

from repro.core import (ControllerConfig, SimConfig, Topology, cube,
                        fully_connected, hourglass, make_links,
                        random_regular, simulate, torus3d)
from repro.core.frame_model import LinkParams
from repro.kernels import simulate_dense_perstep, simulate_fused
# Promoted to the production telemetry package (PR 8) so examples and CLI
# tooling can assert the zero-recompile guarantee outside pytest;
# re-exported here so existing test imports keep working.
from repro.telemetry import engine_cache_sizes, no_new_compiles  # noqa: F401

# ------------------------------------------------------- tolerance policy

FREQ_ATOL_PPM = 1e-6
BETA_ATOL_FRAMES = 1e-6
BETA_ATOL_CROSS_FRAMES = 2e-5
BETA_RTOL_CROSS = 4 * float(np.finfo(np.float32).eps)

# ---------------------------------------------------------- engine matrix

# The compiled kernel lanes (simulate_fused's engine axis).
KERNEL_ENGINES = ["fused", "tiled", "per-step", "sparse"]
# Everything run_scenario accepts.
SCENARIO_ENGINES = ["segment-sum"] + KERNEL_ENGINES


def bounded_degree_topo(n: int, max_deg: int, seed: int = 0,
                        isolated: int = 0, leaves: int = 0) -> Topology:
    """Random bounded-in-degree digraph exercising the sparse lane's
    padding edge cases.

    Node i draws ``1..max_deg`` in-edges from distinct other nodes (node
    0 always draws exactly ``max_deg``, so the ELL table's last slot row
    is never dead); the final ``isolated`` nodes get no edges at all
    (zero-degree ⇒ the controller error is identically 0 and ν must hold
    ν_u) and the ``leaves`` nodes before them exactly one (degree-1 —
    no averaging, pure follow).
    """
    if n < max(3, max_deg + 1):
        raise ValueError("need n > max_deg and n >= 3")
    rng = np.random.default_rng(seed)
    src, dst = [], []
    first_leaf = n - isolated - leaves
    if first_leaf < 1:
        raise ValueError("isolated + leaves must leave >= 1 plain node")
    for i in range(n - isolated):
        if i == 0:
            d = max_deg
        elif i >= first_leaf:
            d = 1
        else:
            d = int(rng.integers(1, max_deg + 1))
        others = np.delete(np.arange(n), i)
        picks = rng.choice(others, size=d, replace=False)
        src.extend(int(p) for p in picks)
        dst.extend([i] * d)
    return Topology(n, np.asarray(src, np.int32), np.asarray(dst, np.int32),
                    name=f"bounded_deg_{n}_{max_deg}_{seed}"
                         f"{'_iso' + str(isolated) if isolated else ''}")


# The paper's evaluated topologies (§5.3–§5.5, Fig 18's torus family), a
# tile-boundary-crossing random-regular graph (n_pad = 384 ⇒ real
# multi-panel accumulation), and a ragged bounded-degree graph whose
# in-degrees span 1..4 (real ELL slot padding on the sparse lane).
PARITY_TOPOS = [fully_connected(8), hourglass(4), cube(), torus3d(4),
                random_regular(300, 3, 0), bounded_degree_topo(96, 4, 3)]

PARITY_STEPS, PARITY_REC, PARITY_KP = 120, 12, 2e-9

# β parity runs in converged bounded-occupancy regimes (the paper's
# operating point): gain high enough that buffers settle within the run
# and |β| stays O(1) frames.  Δ·kp·λ_max stays below 1 on both.
BETA_PARITY_CASES = [
    # (topo, kp, ppm_scale, steps, record_every)
    (fully_connected(8), 2e-7, 0.5, 120, 12),
    (torus3d(8), 6e-7, 0.25, 96, 12),
]


def parity_ppm(topo: Topology, seed: int = 7, scale: float = 8.0):
    """The matrix's shared ±scale ppm oscillator draw."""
    return np.random.default_rng(seed).uniform(-scale, scale,
                                               topo.num_nodes)


def zero_mean_ppm(n: int, scale: float, seed: int = 7):
    """Zero-mean draw: the ensemble frequency consensus is 0, so β stays
    bounded without reframing (the converged-regime β parity setup)."""
    ppm = np.random.default_rng(seed).uniform(-scale, scale, n)
    return (ppm - ppm.mean()).astype(np.float32)


def node_recon(topo: Topology, beta_edges: np.ndarray) -> np.ndarray:
    """(..., N) float64 per-node net occupancy from per-edge (..., E)
    records — the segment-sum reconstruction the in-kernel per-node β
    stream is validated against (optionally weighted by the caller
    pre-multiplying ``beta_edges``)."""
    beta_edges = np.asarray(beta_edges, np.float64)
    out = np.zeros(beta_edges.shape[:-1] + (topo.num_nodes,))
    dst = np.asarray(topo.dst)
    np.add.at(out, (..., dst), beta_edges)
    return out


_SEGSUM_CACHE: dict = {}


def segment_sum_reference(topo: Topology, links: LinkParams, ppm,
                          kp: float = PARITY_KP, steps: int = PARITY_STEPS,
                          rec: int = PARITY_REC, record_beta: bool = False):
    """Segment-sum trajectory at the decimated record points (cached per
    (topology, gains, schedule) so the matrix pays each reference once)."""
    key = (topo.name, float(kp), int(steps), int(rec), bool(record_beta))
    if key not in _SEGSUM_CACHE:
        res = simulate(topo, links, ControllerConfig(kp=kp),
                       np.asarray(ppm, np.float32),
                       SimConfig(dt=1e-3, steps=steps, record_every=rec,
                                 record_beta=record_beta))
        assert res.engine == "segment-sum"
        _SEGSUM_CACHE[key] = res
    return _SEGSUM_CACHE[key]


def run_kernel_engine(topo: Topology, links: LinkParams, ppm, engine: str,
                      steps: int = PARITY_STEPS, rec: int = PARITY_REC,
                      kp: float = PARITY_KP, **kw):
    """Run one kernel lane and return its result with (R, N) freq records.

    The per-step lane records every period; its stream is decimated here
    so every engine's record grid is identical.
    """
    if engine == "per-step":
        res = simulate_dense_perstep(topo, links, ppm, steps=steps, kp=kp,
                                     dt=1e-3)
        return res, res[0][rec - 1::rec]
    res = simulate_fused(topo, links, ppm, steps=steps, kp=kp, dt=1e-3,
                         record_every=rec, engine=engine, **kw)
    return res, res[0]


def assert_freq_parity(freq, ref, atol: float = FREQ_ATOL_PPM):
    np.testing.assert_allclose(np.asarray(freq), np.asarray(ref), rtol=0,
                               atol=atol)


def assert_beta_parity(beta, ref, atol: float = BETA_ATOL_FRAMES):
    np.testing.assert_allclose(np.asarray(beta), np.asarray(ref), rtol=0,
                               atol=atol)


# ----------------------------------------------------- compile-count guard
#
# engine_cache_sizes / no_new_compiles live in repro.telemetry.compile_stats
# now (imported above).


# -------------------------------------------------------- guard-on lane
#
# The in-kernel reframing guard is part of the cross-engine contract:
# all four kernel lanes must trip at the SAME record index (the guard is
# the same degree-scaled band over the same in-kernel β measurement) and
# splice identical rotations, and the guard-variant executables must be
# observation-free — bit-identical outputs when the band is never
# crossed.

def guard_case(n: int = 8, steps: int = 480, rec: int = 12,
               kp: float = 2e-8, rate: float = 40.0,
               depth: int = 16, margin: float = 4.0):
    """A DriftRamp slew that crosses a ``depth``-deep guard band on every
    kernel lane — the guard-on parity case."""
    from repro.core import ReframePolicy
    from repro.scenarios import DriftRamp, Scenario
    topo = fully_connected(n)
    links = make_links(topo, cable_m=2.0)
    ctrl = ControllerConfig(kp=kp)
    cfg = SimConfig(dt=1e-3, steps=steps, record_every=rec)
    ppm = zero_mean_ppm(n, 0.5)
    sc = Scenario(events=(DriftRamp(t=0.06, t_end=0.3, nodes=(0, 1),
                                    rate_ppm_per_s=rate),))
    pol = ReframePolicy(depth=depth, margin=margin)
    return topo, links, ctrl, ppm, sc, cfg, pol


def run_guarded(topo, links, ctrl, ppm, sc, cfg, engine, pol,
                record_beta: bool = True):
    """One scenario lane through the typed API, guard on (``pol`` may be
    None for the guard-off comparison run of the same lane)."""
    from repro.kernels import EngineOptions
    from repro.scenarios import run_scenario
    from repro.telemetry import Telemetry
    return run_scenario(topo, links, ctrl, ppm, sc, cfg,
                        options=EngineOptions(engine=engine),
                        telemetry=Telemetry(beta=record_beta,
                                            guard=pol if pol else False))


# ------------------------------------------- property-test graph builders
#
# ``hypcompat``'s deterministic fallback supports only scalar strategies
# (integers / floats / booleans / sampled_from), so the property tests
# draw scalars and hand them to these builders — identical graphs under
# real hypothesis and the fallback runner.

def random_latency_links(topo: Topology, seed: int,
                         heterogeneous: bool = False) -> LinkParams:
    """Random per-edge cable lengths.

    ``heterogeneous=False`` draws from a small discrete length set (few
    latency classes — every dense lane can run it); ``True`` draws every
    edge's length independently (sparse / segment-sum regime).
    """
    rng = np.random.default_rng(seed)
    if heterogeneous:
        cable = rng.uniform(1.0, 50.0, topo.num_edges)
    else:
        cable = rng.choice([2.0, 10.0, 40.0], size=topo.num_edges)
    return make_links(topo, cable_m=cable)
