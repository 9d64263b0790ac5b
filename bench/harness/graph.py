"""Edge-list helpers shared by the benchmark's graph generators, and the
reordering that draws a build cell's input from ``--seed``."""
from __future__ import annotations

import numpy as np


def pack_pairs(n, u, v):
    """Self-loop-free pairs (lo < hi) as sorted unique keys lo * n + hi."""
    keep = u != v
    lo = np.minimum(u[keep], v[keep]).astype(np.int64)
    hi = np.maximum(u[keep], v[keep]).astype(np.int64)
    return np.unique(lo * np.int64(n) + hi)


def unpack_keys(n, keys):
    return np.stack([keys // n, keys % n], 1)


def finalize(n, und_edges, rng, max_w):
    """Deduplicated undirected pairs with integral weights in [1, max_w],
    returned as ``(n, src, dst, w)`` with both directions."""
    pairs = unpack_keys(n, pack_pairs(n, und_edges[:, 0], und_edges[:, 1]))
    m = pairs.shape[0]
    weights = rng.integers(1, max_w + 1, size=m).astype(np.float32)
    src = np.concatenate([pairs[:, 0], pairs[:, 1]]).astype(np.int32)
    dst = np.concatenate([pairs[:, 1], pairs[:, 0]]).astype(np.int32)
    w = np.concatenate([weights, weights]).astype(np.float32)
    return n, src, dst, w


def reorder(graph, rng):
    """The same graph with its edge list in a random order and each
    undirected pair stored in a random orientation: every size the
    build sees is unchanged, and the build's result with it."""
    n, src, dst, w = graph
    half = len(src) // 2
    order = rng.permutation(half)
    flip = rng.random(half) < 0.5
    a, b = src[:half][order], dst[:half][order]
    s = np.where(flip, b, a)
    d = np.where(flip, a, b)
    ww = w[:half][order]
    return (n, np.concatenate([s, d]), np.concatenate([d, s]),
            np.concatenate([ww, ww]))
