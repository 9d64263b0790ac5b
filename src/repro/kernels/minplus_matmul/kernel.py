"""Tropical (min-plus) matmul Pallas kernel: C[i,j] = min_k A[i,k]+B[k,j].

This is the compute hot-spot of IS-LABEL re-expressed for the TPU: the
paper's block-nested-loop label join (Alg. 4) and the label-seeded core
search are both min-plus products (distance vectors × distance-preserving
adjacency). The MXU only does mul-add, so min-plus runs on the VPU —
the tiling below keeps operand tiles VMEM-resident and hardware-aligned
(multiples of 8×128 lanes) exactly like a dense GEMM, with the k-grid
dimension innermost so each (i,j) output tile accumulates in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _minplus_kernel(a_ref, b_ref, o_ref):
    """Grid = (M/bm, N/bn, K/bk); K innermost (default row-major order)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, jnp.inf, o_ref.dtype)

    a = a_ref[...]                      # [bm, bk]
    b = b_ref[...]                      # [bk, bn]
    # min over k of a[i,k]+b[k,j], unrolled over the static bk: column k
    # of a broadcasts across lanes, row k of b across sublanes, and the
    # VMEM footprint stays at bm*bn instead of bm*bk*bn. (A traced k
    # would need a value-level dynamic slice, which Mosaic cannot lower.)
    acc = jnp.full(o_ref.shape, jnp.inf, o_ref.dtype)
    for k in range(a.shape[1]):
        acc = jnp.minimum(acc, a[:, k:k + 1] + b[k:k + 1, :])
    o_ref[...] = jnp.minimum(o_ref[...], acc)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus_matmul_kernel(a, b, *, bm=128, bn=128, bk=128, interpret=False):
    """A: [M, K], B: [K, N] (M, N, K multiples of the block shape —
    callers pad with +inf; inf is the min-plus zero so padding is exact).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
    )(a, b)
