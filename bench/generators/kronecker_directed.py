"""Directed Graph500 Kronecker (R-MAT) graph: ``2**scale`` vertices and
``edge_factor * 2**scale`` sampled arcs, initiator a, b, c (d = 1 - a -
b - c), each arc kept in the orientation it is generated with (the
R-MAT model of Chakrabarti, Zhan and Faloutsos, SDM 2004, is a directed
graph). Self-loops and duplicate arcs are dropped; an arc and its
reverse are two arcs.

The same random stream as ``kronecker.py``: at one seed its arcs are the
tuples that generator symmetrises. Weights are drawn per arc after them.
"""
from __future__ import annotations

import numpy as np


def generate(cfg: dict, seed: int):
    """``(n, src, dst, w)``: one entry per arc ``src -> dst``, sorted."""
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
    keep = src != dst
    keys = np.unique(src[keep] * np.int64(n) + dst[keep])
    w = rng.integers(1, int(cfg["max_weight"]) + 1,
                     size=len(keys)).astype(np.float32)
    return (n, (keys // n).astype(np.int32), (keys % n).astype(np.int32), w)
