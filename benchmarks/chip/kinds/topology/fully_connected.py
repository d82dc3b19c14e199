"""Fully connected fabric: every node linked to every other (paper §5.3).

``{"kind": "fully_connected", "nodes": n}``; n·(n−1) directed edges.
"""
import numpy as np

# Every permutation of the nodes keeps the fabric.
TRANSITIVE = True


def nodes(t: dict) -> int:
    return int(t["nodes"])


def pairs(t: dict) -> np.ndarray:
    n = nodes(t)
    return np.asarray([(i, j) for i in range(n) for j in range(n) if i != j],
                      np.int64)


def program(t: dict):
    from repro.core import fully_connected
    return fully_connected(nodes(t))
