"""3-D torus of k³ nodes, each linked to its six neighbours (paper Fig. 18).

``{"kind": "torus3d", "k": k}``; node (x, y, z) is (x·k + y)·k + z.
"""
import numpy as np

# Translations map every node to every other and keep the fabric.
TRANSITIVE = True


def nodes(t: dict) -> int:
    return int(t["k"]) ** 3


def pairs(t: dict) -> np.ndarray:
    k = int(t["k"])
    out = []
    for x in range(k):
        for y in range(k):
            for z in range(k):
                me = (x * k + y) * k + z
                for dx, dy, dz in ((1, 0, 0), (0, 1, 0), (0, 0, 1)):
                    nb = (((x + dx) % k) * k + (y + dy) % k) * k + (
                        z + dz) % k
                    out += [(me, nb), (nb, me)]
    return np.asarray(out, np.int64)


def program(t: dict):
    from repro.core import torus3d
    return torus3d(int(t["k"]))
