"""Time per call copying each chunk's results to the host and slicing,
transposing and folding them: the call's RunTrace ``chunk.fetch``
spans, mean over the window's calls."""
from chipbench.spans import span_ms

KINDS = ("chunk.fetch",)


def read(r):
    return span_ms(r, KINDS)
