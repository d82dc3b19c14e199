"""Host time per call building the dense adjacency stacks or the sparse
slot tables and placing them on the device: the call's RunTrace
``segment.stacks`` spans (their ``segment.upload`` inside), mean over
the window's calls."""
from chipbench.spans import span_ms

KINDS = ("segment.stacks",)


def read(r):
    return span_ms(r, KINDS)
