"""The run's host allocator, fixed so that every process reuses its buffers.

glibc's ``malloc`` serves a block above its mmap threshold from fresh
pages, and gives the heap's top back to the system above its trim
threshold.  Both thresholds move as blocks are freed, so where a process
settles depends on the order of its first allocations: in some processes
each call's host arrays (the results read back from the device, the
tables uploaded to it) land on fresh pages that fault in again on every
call.  ``fix_allocator`` pins both thresholds before anything large is
allocated, so every run keeps and reuses the same heap.  It changes no
result, only where the process's host memory comes from.
"""
from __future__ import annotations

import ctypes
import ctypes.util

M_TRIM_THRESHOLD = -1   # mallopt parameters, from glibc's malloc.h
M_TOP_PAD = -2
M_MMAP_THRESHOLD = -3

SETTINGS = ((M_MMAP_THRESHOLD, 32 << 20),   # glibc's largest: 32 MiB
            (M_TRIM_THRESHOLD, 1 << 30),
            (M_TOP_PAD, 64 << 20))


def fix_allocator() -> bool:
    """Pin glibc's thresholds; False where the C library has no
    ``mallopt`` or refuses a setting."""
    try:
        libc = ctypes.CDLL(ctypes.util.find_library("c") or "libc.so.6")
        mallopt = libc.mallopt
    except (OSError, AttributeError):
        return False
    return all(mallopt(k, v) == 1 for k, v in SETTINGS)
