"""Pallas TPU kernels for the bittide simulation hot-spot.

bittide_step    pl.pallas_call kernels: per-step baseline + fused multi-period
                batched engine (VMEM-resident adjacency, scratch-carried state,
                in-kernel telemetry decimation) + tiled fused engine (adjacency
                streamed from HBM in double-buffered column panels for
                Fig-18-scale networks) + the select_engine dispatch heuristic.
                Controller gains, per-draw class latencies, per-draw λeff
                folds and the per-node controller-enable mask are all traced
                inputs — scenario segments and Monte-Carlo link draws reuse
                one compiled kernel.
period          the period body the Pallas lanes share: controller law,
                β measurement, watermarks, guard, early exit, and the
                wrappers' guard inputs and output layout.
bittide_sparse  edge-major ELL engine: per-node state resident, (K, N) slot
                tables (neighbor / per-edge latency / weight) streamed in
                i-panels — O(N·deg) per period for bounded-degree graphs
                (~5·10⁴ nodes at B = 8 on a TPU), with per-draw edge
                weights and fully heterogeneous per-draw latencies as
                traced inputs.
ops             jit wrappers + topology densification (fixed-class, weighted)
                + fused/ensemble runners (init-state chaining, per-draw link
                parameters; DenseResult path metadata + exact .nu)
ref             pure-jnp oracles the kernels are validated against
api             EngineOptions (typed engine knobs, accepted as ``options=``)
                and EngineOutputs (the named engine-lane return replacing
                the positional 5-tuple)
"""
from .api import EngineOptions, EngineOutputs, resolve_options
from .bittide_sparse import bittide_sparse_pallas, ellify, max_in_degree
from .bittide_step import (RESIDENT_N_MAX, SUBLANE, TILE, TILE_J_MAX,
                           bittide_fused_pallas, bittide_step_pallas,
                           bittide_tiled_fused_pallas, fused_vmem_bytes,
                           select_engine, sparse_vmem_bytes,
                           tiled_vmem_bytes)
from .ops import (DenseResult, bittide_step, densify, latency_classes,
                  simulate_dense, simulate_dense_perstep,
                  simulate_ensemble_dense, simulate_fused)
from .ref import (bittide_dense_multistep_ref, bittide_dense_step_ref,
                  occupancy_ref)
