"""COO core graph -> ELL (fixed-width in-neighbor lists), the layout
every stage-2 route but the COO reference consumes."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def ell_layout(n_v: int, dst, d_width: int = 16):
    """Slot assignment for the ELL conversion: stable-sort edges by dst,
    each edge's slot is its rank within the dst group (position minus
    the group's CSR offset). Returns ``(order, rows, slots, width)`` so
    callers can scatter any per-edge payload (weights, via vertices for
    path reconstruction) into identically-aligned ELL planes.
    """
    dst = np.asarray(dst, np.int64)
    indeg = np.bincount(dst, minlength=n_v)
    width = max(d_width, int(-(-max(1, indeg.max(initial=0)) // d_width)
                             * d_width))
    if len(dst) == 0:
        empty = np.zeros(0, np.int64)
        return empty, empty, empty, width
    order = np.argsort(dst, kind="stable")
    d_sorted = dst[order]
    indptr = np.concatenate([[0], np.cumsum(indeg)])
    rank = np.arange(len(dst), dtype=np.int64) - indptr[d_sorted]
    return order, d_sorted, rank, width


def coo_to_ell(n_v: int, src, dst, w, d_width: int = 16):
    """Convert COO (src -> dst relaxation direction) into ELL rows of
    width d_width. Vertices with in-degree > d_width get *duplicate ELL
    row groups* folded via extra virtual rounds — here we instead grow
    the width to the max in-degree rounded up to a multiple of d_width
    (simple and exact; G_k degrees are bounded in practice).
    """
    src = np.asarray(src, np.int32)
    w = np.asarray(w, np.float32)
    order, rows, slots, width = ell_layout(n_v, dst, d_width)
    ids = np.zeros((n_v, width), np.int32)
    ws = np.full((n_v, width), np.inf, np.float32)
    if len(src):
        ids[rows, slots] = src[order]
        ws[rows, slots] = w[order]
    return jnp.asarray(ids), jnp.asarray(ws)

