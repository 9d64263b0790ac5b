"""What a run draws from its seed.

A build cell's traffic is its input: the configuration's graph with
its edge list reordered from ``--seed`` (``harness/graph.py``
``reorder``), and the pairs its check asks, drawn among the vertices
with at least one edge, as Graph500 draws its search keys.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole-number seed, negative or past 64 bits
    included; ``stream`` separates independent draws from one seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def linked(graph) -> np.ndarray:
    """The vertices of ``(n, src, dst, w)`` with at least one edge."""
    n, src, dst, _ = graph
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    return np.flatnonzero(deg).astype(np.int32)
