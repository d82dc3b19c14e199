"""Operations and bytes one call needs on the tiled lane.

The tiled lane streams the (C, N, N) float32 adjacency from HBM in column
panels once per control period, and once more per record when β or the
watermarks are measured.  Each sweep is a (B, N) x (N, N) contraction
per latency class.  Counted at the unpadded B and N, without the state
and record traffic: a lower bound.
"""

# The period kernel in the device trace: the Pallas call is a custom
# call with this target inside the engine's jit (the trace gives it no
# name of its own); the lane comes from the result.
TRACE_NAMES = ['custom_call_target="tpu_custom_call"']


def count(s: dict) -> dict:
    sweeps = s["periods"] + (s["records"] if s["measure"] else 0)
    n2c = s["nodes"] ** 2 * s["classes"]
    return {"flops": 2 * s["draws"] * n2c * sweeps, "bytes": 4 * n2c * sweeps}
