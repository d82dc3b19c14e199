"""Host time per call compiling the scenario and preparing each segment
at its start (λeff fold, padding, uploads): the call's RunTrace
``segment.compile`` and ``segment.prep`` spans, mean over the window's
calls."""
from chipbench.spans import span_ms

KINDS = ("segment.compile", "segment.prep")


def read(r):
    return span_ms(r, KINDS)
