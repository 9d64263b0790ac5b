"""Graph500 Kronecker (R-MAT) graph: ``2**scale`` vertices and
``edge_factor * 2**scale`` sampled edges, initiator a, b, c (d = 1 - a -
b - c), self-loops and duplicates dropped.

A copy of ``rmat_graph`` in ``src/repro/graphs/generators.py`` (same
random stream at one chunk, so the same seed gives the same graph), kept
here so the benchmark makes its data without calling the code under
test.
"""
from __future__ import annotations

import numpy as np

from harness.graph import finalize, pack_pairs, unpack_keys


def generate(cfg: dict, seed: int):
    """``(n, src, dst, w)`` with both directions of every edge."""
    scale = int(cfg["scale"])
    a, b, c = (float(x) for x in cfg["initiator"][:3])
    n = 1 << scale
    m = int(cfg["edge_factor"]) * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(scale):
        q = rng.random(m)
        sbit = (q >= a + b).astype(np.int64)
        dbit = ((q >= a) & (q < a + b) | (q >= a + b + c)).astype(np.int64)
        src = (src << 1) | sbit
        dst = (dst << 1) | dbit
    pairs = unpack_keys(n, pack_pairs(n, src, dst))
    return finalize(n, pairs, rng, int(cfg["max_weight"]))
