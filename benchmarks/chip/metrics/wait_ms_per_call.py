"""Time per call blocked until the engine's outputs are ready, the
device's work and the transfers queued ahead of it: the call's RunTrace
``chunk.wait`` spans, mean over the window's calls."""
from chipbench.spans import span_ms

KINDS = ("chunk.wait",)


def read(r):
    return span_ms(r, KINDS)
