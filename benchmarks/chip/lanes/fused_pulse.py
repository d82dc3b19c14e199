"""Operations and bytes one call needs on the fused (resident) lane under
FINC/FDEC with integer buffer readout.

Every control period each edge's occupancy is formed and rounded alone:
the source term ψ_src − ν_src·lat_e (one (B, N) x (N, E) one-hot
contraction per latency class), the destination phase ψ_dst (one more),
and the rounded occupancies summed by destination ((B, E) x (E, N)).
Once per record, when β or the watermarks are measured, the unrounded
per-node occupancy takes one (B, N) x (N, N) contraction per class on
the resident adjacency.  HBM sees the adjacency and the edge matrices
once and the records once.  Counted at the unpadded B, N and E: a lower
bound.
"""

# The period kernel in the device trace: the Pallas call is a custom
# call with this target inside the engine's jit (the trace gives it no
# name of its own); the lane comes from the result.
TRACE_NAMES = ['custom_call_target="tpu_custom_call"']


def count(s: dict) -> dict:
    b, n, e, c = s["draws"], s["nodes"], s["edges"], s["classes"]
    measure = s["records"] if s["measure"] else 0
    flops = (2 * b * n * e * (c + 2) * s["periods"]
             + 2 * b * n * n * c * measure)
    streams = 2 if s["measure"] else 1
    return {"flops": flops,
            "bytes": 4 * (n * n * c + n * e * (c + 2) + e)
            + 4 * b * n * s["records"] * streams}
