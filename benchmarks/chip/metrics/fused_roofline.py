"""The fused (resident) period kernel's share of its roofline: counted
at the unpadded shapes, so lane padding shows as a low share."""
from chipbench.roofline import share


def read(r):
    return share(r, "fused")
