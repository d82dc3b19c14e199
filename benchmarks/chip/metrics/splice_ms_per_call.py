"""Host time per call in splices: re-establishes with their live-state
reads, guard margins and Laplacian inverses, and pointer rotations with
the re-prep after them.  The call's RunTrace ``segment.splice``,
``guard`` and ``reframe`` spans, mean over the window's calls."""
from chipbench.spans import span_ms

KINDS = ("segment.splice", "guard", "reframe")


def read(r):
    return span_ms(r, KINDS)
