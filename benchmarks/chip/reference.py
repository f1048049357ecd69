"""The plain reference: what a graph store must answer after a prefix of
the op stream. numpy only; it imports nothing of the program.

Copied from ``chip_smoke.Reference`` and generalised from "the whole
stream" to any op prefix, so that a change to the smoke cannot move it.
Semantics: an undirected op (u, v, w) lands as the directed pair (u, v)
then (v, u); the last op on a directed pair wins; weight 0 is a delete.
Every endpoint an op names is a registered vertex.
"""
from __future__ import annotations

import numpy as np


class Reference:
    """Last-writer-wins directed edge set of an undirected op prefix, as a
    CSR over vertex indices."""

    def __init__(self, V: int, u, v, w, undirected: bool = True):
        u = np.asarray(u, np.int64)
        v = np.asarray(v, np.int64)
        w = np.asarray(w, np.float32)
        if undirected:
            key = np.empty(2 * len(u), np.int64)
            key[0::2] = u * V + v
            key[1::2] = v * V + u
            ww = np.repeat(w, 2)
        else:
            key, ww = u * V + v, w
        # last occurrence of each directed pair wins
        uk, first = np.unique(key[::-1], return_index=True)
        last_w = ww[::-1][first]
        live = last_w != 0
        self.key, self.w = uk[live], last_w[live]        # sorted by (u, v)
        self.src = self.key // V
        self.dst = self.key % V
        self.indptr = np.zeros(V + 1, np.int64)
        np.cumsum(np.bincount(self.src, minlength=V), out=self.indptr[1:])
        self.V = V
        self.present = np.zeros(V, bool)
        self.present[u] = True
        self.present[v] = True

    @property
    def num_edges(self) -> int:
        return len(self.key)

    def degree(self, x):
        return self.indptr[np.asarray(x) + 1] - self.indptr[np.asarray(x)]

    def neighbors(self, x: int):
        """(destination indices ascending, weights) of vertex ``x``."""
        lo, hi = self.indptr[x], self.indptr[x + 1]
        return self.dst[lo:hi], self.w[lo:hi]

    def pagerank(self, iters: int, damping: float = 0.85) -> np.ndarray:
        """float64 PageRank over the registered vertices; a vertex with no
        out-edge spreads its rank over all of them."""
        n = float(self.present.sum())
        deg = np.diff(self.indptr).astype(np.float64)
        pr = np.where(self.present, 1.0 / n, 0.0)
        for _ in range(iters):
            contrib = np.where(deg > 0, pr / np.maximum(deg, 1.0), 0.0)
            dangling = pr[self.present & (deg == 0)].sum()
            inflow = np.bincount(self.dst, weights=contrib[self.src],
                                 minlength=self.V)
            pr = np.where(self.present,
                          (1 - damping) / n + damping * (inflow + dangling / n),
                          0.0)
        return pr
