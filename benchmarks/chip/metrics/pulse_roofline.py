"""The fused period kernel's share of its roofline under FINC/FDEC with
integer readout, counted by ``lanes/fused_pulse.py`` at the unpadded
shapes, so lane padding shows as a low share; None off the fused lane."""
import dataclasses

from chipbench.roofline import share


def read(r):
    if r.lane != "fused":
        return None
    return share(dataclasses.replace(r, lane="fused_pulse"), "fused_pulse")
