"""The plain reference accepts exactly the true distances."""
import heapq
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.graph import finalize, reorder  # noqa: E402
from harness.reference import Reference  # noqa: E402


def _dijkstra(n, src, dst, w, s):
    adj = [[] for _ in range(n)]
    for a, b, c in zip(src, dst, w):
        adj[a].append((b, float(c)))
    dist = [float("inf")] * n
    dist[s] = 0.0
    heap = [(0.0, s)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for v, c in adj[u]:
            if d + c < dist[v]:
                dist[v] = d + c
                heapq.heappush(heap, (d + c, v))
    return dist


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_accepts_exactly_the_true_distance(seed):
    rng = np.random.default_rng(seed)
    n = 300
    g = finalize(n, rng.integers(0, n, (330, 2)), rng, 4)
    ref = Reference(g)
    for s in rng.integers(0, n, 12):
        dist = _dijkstra(*g, int(s))
        for t in rng.integers(0, n, 12):
            d = dist[t]
            assert ref.check(s, t, d)
            if np.isfinite(d):
                assert not ref.check(s, t, d + 1)
                assert not ref.check(s, t, float("inf"))
                if d > 0:
                    assert not ref.check(s, t, d - 1)
            else:
                assert not ref.check(s, t, 5.0)
            assert not ref.check(s, t, float("nan"))


def test_reorder_keeps_the_graph():
    rng = np.random.default_rng(0)
    g = finalize(100, rng.integers(0, 100, (300, 2)), rng, 4)
    h = reorder(g, np.random.default_rng(5))
    assert h[0] == g[0]
    assert not np.array_equal(h[1], g[1])

    def edges(x):
        return sorted(zip(x[1].tolist(), x[2].tolist(), x[3].tolist()))
    assert edges(h) == edges(g)
