"""The tiled period kernel's share of its roofline (HBM-bound at the
Fig-18 torus: it streams the adjacency panels every period)."""
from chipbench.roofline import share


def read(r):
    return share(r, "tiled")
