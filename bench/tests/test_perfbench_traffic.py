"""A build cell's traffic: the same seed gives the same input and the
same pairs, any whole-number seed works, and every seed builds the same
graph."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import traffic  # noqa: E402
from harness.graph import finalize, reorder  # noqa: E402


def _graph():
    rng = np.random.default_rng(0)
    return finalize(200, rng.integers(0, 150, (600, 2)), rng, 4)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**64 + 3, -5])
def test_same_seed_same_input(seed):
    g = _graph()
    a = reorder(g, traffic.rng_for(seed))
    b = reorder(g, traffic.rng_for(seed))
    assert all(np.array_equal(x, y) for x, y in zip(a[1:], b[1:]))
    c = reorder(g, traffic.rng_for(seed + 1))
    assert not np.array_equal(a[1], c[1])


def test_streams_of_one_seed_differ():
    a = traffic.rng_for(3, 0).integers(0, 1 << 30, 8)
    b = traffic.rng_for(3, 1).integers(0, 1 << 30, 8)
    assert not np.array_equal(a, b)


def test_linked_vertices_are_those_with_an_edge():
    # vertices 0..9, edges among 2..5 only (both directions stored)
    src = np.array([2, 3, 4, 3, 4, 5], np.int32)
    dst = np.array([3, 4, 5, 2, 3, 4], np.int32)
    graph = (10, src, dst, np.ones(6, np.float32))
    assert traffic.linked(graph).tolist() == [2, 3, 4, 5]
    g = _graph()
    assert set(traffic.linked(g)) == set(g[1].tolist())
