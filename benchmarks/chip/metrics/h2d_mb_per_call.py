"""Bytes per call placed on the device from the host, padded as they
move (stacks or slot tables, segment prep): the call's RunTrace
``h2d_bytes`` counter in MB (1e6 bytes), mean over the window's calls."""
from chipbench.spans import counter_mb

COUNTER = "h2d_bytes"


def read(r):
    return counter_mb(r, COUNTER)
