"""Host time per call in the reframing guard: its margins, the Laplacian
pseudo-inverse (O(N³), once a call) and the in-kernel guard band.  The
call's RunTrace ``guard`` spans that no ``guard`` or ``reframe`` span
encloses, mean over the window's calls."""
from chipbench.spans import outer_span_ms

KINDS = ("guard",)
NEST = ("guard", "reframe")


def read(r):
    return outer_span_ms(r, KINDS, NEST)
