"""A kernel's share of its roofline, from the lane's counts and the trace.

The least time the chip could take for the profiled calls is the larger
of their operations over the peak FLOP/s and their bytes over the HBM
bandwidth; the share is that over the kernel's device time.  The counts
(``lanes/<lane>.py``) are lower bounds, so the share cannot pass 100 %
unless the trace leaves out part of the kernel's time.
"""
from __future__ import annotations

from typing import Optional


def share(r, lane: str) -> Optional[float]:
    """Percent of the roofline, or None where the lane did not run."""
    if r.profile is None or r.lane != lane:
        return None
    kernel_s = r.profile.kernel_s.get(lane, 0.0)
    if kernel_s <= 0:
        return None
    c = r.count(lane)
    t_flops = c["flops"] / r.peaks["bf16_flops_per_s"]
    t_bytes = c["bytes"] / r.peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) * r.profile.calls / kernel_s
