"""Operations and bytes one call needs on the fused (resident) lane.

The fused lane keeps the (C, N, N) float32 adjacency and the (B, N)
state in VMEM for the whole launch: per control period, and once more
per record when β or the watermarks are measured, one (B, N) x (N, N)
contraction per latency class.  HBM sees the adjacency once and the
records once.  Counted at the unpadded B and N: a lower bound.
"""

# The period kernel in the device trace: the Pallas call is a custom
# call with this target inside the engine's jit (the trace gives it no
# name of its own); the lane comes from the result.
TRACE_NAMES = ['custom_call_target="tpu_custom_call"']


def count(s: dict) -> dict:
    sweeps = s["periods"] + (s["records"] if s["measure"] else 0)
    n2c = s["nodes"] ** 2 * s["classes"]
    streams = 2 if s["measure"] else 1
    return {"flops": 2 * s["draws"] * n2c * sweeps,
            "bytes": 4 * n2c + 4 * s["draws"] * s["nodes"] * s["records"]
            * streams}
