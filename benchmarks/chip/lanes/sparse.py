"""Operations and bytes one call needs on the sparse (ELL) lane.

Per control period, and once more per record when β or the watermarks
are measured, every directed edge contributes ψ_src − ν_src·l to its
destination: a multiply and two adds per draw and edge.  HBM sees the
slot tables (neighbour, latency, weight) once and the records once.
Counted at the unpadded B, N and E: a lower bound.
"""

# The period kernel in the device trace: the Pallas call is a custom
# call with this target inside the engine's jit (the trace gives it no
# name of its own); the lane comes from the result.
TRACE_NAMES = ['custom_call_target="tpu_custom_call"']


def count(s: dict) -> dict:
    sweeps = s["periods"] + (s["records"] if s["measure"] else 0)
    streams = 2 if s["measure"] else 1
    return {"flops": 3 * s["draws"] * s["edges"] * sweeps,
            "bytes": 12 * s["edges"]
            + 4 * s["draws"] * s["nodes"] * s["records"] * streams}
