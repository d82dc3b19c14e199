"""Bytes per call read back from the device, padded as they move (chunk
records and watermarks, live and final state): the call's RunTrace
``d2h_bytes`` counter in MB (1e6 bytes), mean over the window's calls."""
from chipbench.spans import counter_mb

COUNTER = "d2h_bytes"


def read(r):
    return counter_mb(r, COUNTER)
