"""The plain reference: exact point-to-point distances by Dijkstra.

It imports nothing of the program under test and uses only the graph
the benchmark generated. ``Reference.check(s, t, served)`` decides
whether a served distance is the exact one:

* ``served`` finite: Dijkstra from ``s`` and from ``t`` (scipy's, in C),
  each cut at radius ``served / 2``. Every shortest path of length
  ``D <= served`` has an edge (u, v) with ``d(s, u) <= D / 2`` and
  ``d(v, t) < D / 2``, so the least ``d(s, u) + w + d(v, t)`` over the
  edges both searches reached is ``D``; any candidate is the length of a
  real path. The served distance is exact if and only if that least
  value equals it.
* ``served`` infinite: exact if and only if ``s`` and ``t`` lie in
  different connected components.

Weights are integers, so sums are exact in float32 and float64 alike and
the comparison is equality.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csg


class Reference:
    def __init__(self, graph):
        n, src, dst, w = graph
        self.n = int(n)
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.w = np.asarray(w, np.float64)
        # duplicate (src, dst) entries would be summed by csr: keep the
        # lightest of each
        order = np.lexsort((self.w, self.dst, self.src))
        key = self.src[order] * self.n + self.dst[order]
        first = np.ones(len(key), bool)
        first[1:] = key[1:] != key[:-1]
        keep = order[first]
        self.adj = sp.csr_matrix(
            (self.w[keep], (self.src[keep], self.dst[keep])),
            shape=(self.n, self.n))
        self.adj_rev = self.adj.T.tocsr()
        _, self.component = csg.connected_components(self.adj,
                                                     directed=True,
                                                     connection="strong")

    def distance_within(self, s: int, t: int, radius: float) -> float:
        """The s-t distance if it is at most ``2 * radius``, else a value
        above it (a real path's length) or infinity."""
        limit = float(radius) + 0.25       # distances are integers
        ds = csg.dijkstra(self.adj, directed=True, indices=s, limit=limit)
        dt = csg.dijkstra(self.adj_rev, directed=True, indices=t,
                          limit=limit)
        best = min(ds[t], dt[s])
        # the edges leaving the vertices the forward search reached
        rows = np.flatnonzero(np.isfinite(ds))
        out = self.adj[rows].tocoo()
        cand = ds[rows[out.row]] + out.data + dt[out.col]
        if len(cand):
            best = min(best, float(np.min(cand)))
        return float(best)

    def check(self, s: int, t: int, served: float) -> bool:
        s, t, served = int(s), int(t), float(served)
        if np.isnan(served) or served < 0:
            return False
        if np.isinf(served):
            return bool(self.component[s] != self.component[t])
        return self.distance_within(s, t, served / 2.0) == served
