"""Host time per call in pointer rotations: the live-state read, the
graph-mode shifts (an (N, N) pseudo-inverse times each tripped draw's
net occupancy) and the segment re-prep after them.  The call's RunTrace
``reframe`` spans that no ``guard`` or ``reframe`` span encloses, mean
over the window's calls."""
from chipbench.spans import outer_span_ms

KINDS = ("reframe",)
NEST = ("guard", "reframe")


def read(r):
    return outer_span_ms(r, KINDS, NEST)
