"""Program side of the random geometric graph configurations.

The benchmark makes the graph itself: points uniform in the unit square
from the configuration's fixed ``graph_seed`` (the collection's instance
is one fixed graph), every pair within the radius found by a KD-tree.  It
hands the program the shifted Laplacian L + shift * mean(deg) * I as COO
arrays in the layout of the library's ``graph_laplacian``: the diagonal
first, then each edge as (i, j) and as (j, i), rows unsorted.
"""
from __future__ import annotations

import math

import numpy as np


def make_graph(n: int, radius_factor: float, seed: int):
    """Points uniform in the unit square and every pair within
    r = radius_factor * sqrt(ln n / n), each undirected edge once."""
    from scipy.spatial import cKDTree
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    r = radius_factor * math.sqrt(math.log(n) / n)
    pairs = cKDTree(pts).query_pairs(r, output_type="ndarray")
    return pairs[:, 0].astype(np.int64), pairs[:, 1].astype(np.int64)


def laplacian_coo(n: int, ei, ej, shift: float):
    deg = np.bincount(np.concatenate([ei, ej]), minlength=n).astype(np.float64)
    gamma = shift * max(float(deg.mean()), 1.0)
    rows = np.concatenate([np.arange(n), ei, ej])
    cols = np.concatenate([np.arange(n), ej, ei])
    vals = np.concatenate([deg + gamma, -np.ones(2 * len(ei))])
    return vals, rows, cols


class System:
    def __init__(self, cfg: dict, seed: int, rehearse: bool):
        from repro.core.sparse import SparseTensor
        self.n = int(cfg["rehearse"]["n"] if rehearse else cfg["n"])
        self.ei, self.ej = make_graph(self.n, float(cfg["radius_factor"]),
                                      int(cfg["graph_seed"]))
        self.shift = float(cfg["shift"])
        vals, rows, cols = laplacian_coo(self.n, self.ei, self.ej, self.shift)
        props = {"symmetric": True, "spd_hint": True, "sorted_rows": False,
                 "struct_full_diag": True}
        self.A = SparseTensor(vals.astype(np.float32), rows, cols,
                              (self.n, self.n), props=props)

    def ref_data(self) -> dict:
        return {"n": self.n, "ei": self.ei, "ej": self.ej, "shift": self.shift}

    def release(self) -> None:
        self.A = None
