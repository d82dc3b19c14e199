"""Time per call in the engine calls until they return, launches
enqueued: the call's RunTrace ``chunk.dispatch`` spans, mean over the
window's calls."""
from chipbench.spans import span_ms

KINDS = ("chunk.dispatch",)


def read(r):
    return span_ms(r, KINDS)
