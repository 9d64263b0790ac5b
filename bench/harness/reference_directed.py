"""The plain reference of a directed graph: exact point-to-point
distances ``s -> t`` by Dijkstra over the arcs as given.

A copy of ``reference.py`` for arcs. It imports nothing of the program
under test and uses only the graph the benchmark generated.
``DirectedReference.check(s, t, served)`` decides whether a served
distance is the exact one:

* ``served`` finite: Dijkstra forward from ``s`` and backward (over the
  reversed arcs) from ``t``, each cut at radius ``served / 2``. Every
  shortest path of length ``D <= served`` has an arc (u, v) with
  ``d(s, u) <= D / 2`` and ``d(v, t) < D / 2``, so the least
  ``d(s, u) + w + d(v, t)`` over the arcs leaving the vertices the
  forward search reached is ``D``; any candidate is the length of a real
  path. The served distance is exact if and only if that least value
  equals it.
* ``served`` infinite: exact if and only if a breadth-first search from
  ``s`` never reaches ``t``. (Different strongly connected components do
  not say it: ``s`` may still reach ``t``.)

Weights are integers, so sums are exact in float32 and float64 alike and
the comparison is equality.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csg


class DirectedReference:
    def __init__(self, graph):
        n, src, dst, w = graph
        self.n = int(n)
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        w = np.asarray(w, np.float64)
        # duplicate (src, dst) entries would be summed by csr: keep the
        # lightest of each
        order = np.lexsort((w, dst, src))
        key = src[order] * self.n + dst[order]
        first = np.ones(len(key), bool)
        first[1:] = key[1:] != key[:-1]
        keep = order[first]
        self.adj = sp.csr_matrix((w[keep], (src[keep], dst[keep])),
                                 shape=(self.n, self.n))
        self.adj_rev = self.adj.T.tocsr()

    def reaches(self, s: int, t: int) -> bool:
        """Whether a breadth-first search over the arcs from ``s``
        reaches ``t``."""
        seen = csg.breadth_first_order(self.adj, s, directed=True,
                                       return_predecessors=False)
        return bool(np.isin(t, seen))

    def distance_within(self, s: int, t: int, radius: float) -> float:
        """The s -> t distance if it is at most ``2 * radius``, else a
        value above it (a real path's length) or infinity."""
        limit = float(radius) + 0.25       # distances are integers
        ds = csg.dijkstra(self.adj, directed=True, indices=s, limit=limit)
        dt = csg.dijkstra(self.adj_rev, directed=True, indices=t,
                          limit=limit)
        best = min(ds[t], dt[s])
        # the arcs leaving the vertices the forward search reached
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
            return not self.reaches(s, t)
        return self.distance_within(s, t, served / 2.0) == served
