"""Batched label-intersection Pallas kernel (the query hot path).

Per query q: μ[q] = min over common ancestor ids of d_s + d_t, over two
id-sorted label rows (paper Equation 1). The paper's sequential sorted
merge is branch-heavy; on TPU we do a *rotating equality join*: the
[bq, L] t tile is rotated along the lane axis one slot at a time
(``pltpu.roll``), so after L rotations every (s slot, t slot) pair has
met once in an elementwise compare, min-reducing d_s+d_t where ids
match. O(L^2 / lane_width) fully-vectorized VPU work beats a
data-dependent merge on this hardware, and every operand stays a
lane-dense 2-D tile (no gathers, no value-level dynamic slices).

``label_intersect_packed_kernel`` is the same join over *compressed*
label rows (``repro.core.labels`` delta16 codec): int16 delta planes +
int32 row bases (+ int32 distances when weights are integral) stream in
at 2–4 bytes per entry instead of 8, and the decode — a prefix sum over
the row axis, done with log2(L) lane rotations — happens in-register
before the join. Serving reads the compressed blocks directly; nothing
materializes the fp32 planes in HBM.

Both kernels write μ as a lane-broadcast ``[Q, 128]`` block (TPU
blocks tile at (8, 128)); the wrappers slice column 0.

VMEM per grid step: 4 x [bq, L] operands, double-buffered, plus the
[bq, L] running minimum (bq=8, L=512 -> ~150 KB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.labels import decode_d, decode_ids

LANES = 128


def _lane_prefix_sum(x):
    """Inclusive prefix sum along the lane axis (Hillis-Steele over
    ``pltpu.roll``, whose semantics are ``jnp.roll``'s): the in-kernel
    stand-in for ``jnp.cumsum``, which Mosaic does not lower. Integer
    adds, so the result is bitwise that of ``jnp.cumsum``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    shift = 1
    while shift < x.shape[-1]:
        x = x + jnp.where(lane >= shift,
                          pltpu.roll(x, shift, x.ndim - 1), 0)
        shift *= 2
    return x


def _equality_join(ids_s, d_s, ids_t, d_t, *, n_sentinel):
    """μ over one [bq, L] tile pair as a lane-broadcast [bq, 128] block
    — shared by both kernel variants."""
    bq, l = ids_s.shape
    # pad slots of s become -1, which no t id (real >= 0, pad = n)
    # matches: one compare per pair instead of two
    ids_s = jnp.where(ids_s < n_sentinel, ids_s, -1)

    def body(k, acc):
        it = pltpu.roll(ids_t, k, 1)
        dt = pltpu.roll(d_t, k, 1)
        return jnp.minimum(acc, jnp.where(ids_s == it, d_s + dt, jnp.inf))

    acc = jax.lax.fori_loop(0, l, body,
                            jnp.full((bq, l), jnp.inf, jnp.float32))
    mu = jnp.min(acc, axis=1, keepdims=True)
    return jnp.broadcast_to(mu, (bq, LANES))


def _intersect_kernel(ids_s_ref, d_s_ref, ids_t_ref, d_t_ref, mu_ref, *,
                      n_sentinel):
    mu_ref[...] = _equality_join(ids_s_ref[...], d_s_ref[...],
                                 ids_t_ref[...], d_t_ref[...],
                                 n_sentinel=n_sentinel)


@functools.partial(jax.jit,
                   static_argnames=("n_sentinel", "bq", "interpret"))
def label_intersect_kernel(ids_s, d_s, ids_t, d_t, *, n_sentinel: int,
                           bq=8, interpret=False):
    """ids_*: int32[Q, L] sorted ancestor ids (pad = n_sentinel);
    d_*: float32[Q, L]. Q % bq == 0, L % 128 == 0 (ops.py pads).
    Returns mu float32[Q, 128], every lane of a row equal."""
    q, l = ids_s.shape
    assert q % bq == 0 and l % LANES == 0
    kern = functools.partial(_intersect_kernel, n_sentinel=n_sentinel)
    row_spec = pl.BlockSpec((bq, l), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=(q // bq,),
        in_specs=[row_spec] * 4,
        out_specs=pl.BlockSpec((bq, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, LANES), jnp.float32),
        interpret=interpret,
    )(ids_s, d_s, ids_t, d_t)


def _intersect_packed_kernel(delta_s_ref, base_s_ref, d_s_ref,
                             delta_t_ref, base_t_ref, d_t_ref, mu_ref, *,
                             n_sentinel):
    ids_s = decode_ids(delta_s_ref[...], base_s_ref[...], n_sentinel,
                       cumsum=_lane_prefix_sum)
    ids_t = decode_ids(delta_t_ref[...], base_t_ref[...], n_sentinel,
                       cumsum=_lane_prefix_sum)
    mu_ref[...] = _equality_join(ids_s, decode_d(d_s_ref[...]),
                                 ids_t, decode_d(d_t_ref[...]),
                                 n_sentinel=n_sentinel)


@functools.partial(jax.jit,
                   static_argnames=("n_sentinel", "bq", "interpret"))
def label_intersect_packed_kernel(delta_s, base_s, d_s, delta_t, base_t,
                                  d_t, *, n_sentinel: int, bq=16,
                                  interpret=False):
    """Compressed-row variant: delta_*: int16[Q, L] (pad marker -1),
    base_*: int32[Q, 1], d_*: int32 (pad -1 = +inf) or float32[Q, L].
    Decode is fused before the join — the fp32 planes never exist in
    HBM. bq defaults to 16: int16 operands tile at (16, 128) on TPU.
    Returns mu float32[Q, 128], every lane of a row equal."""
    q, l = delta_s.shape
    assert q % bq == 0 and l % LANES == 0
    kern = functools.partial(_intersect_packed_kernel, n_sentinel=n_sentinel)
    row_spec = pl.BlockSpec((bq, l), lambda i: (i, 0))
    base_spec = pl.BlockSpec((bq, 1), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=(q // bq,),
        in_specs=[row_spec, base_spec, row_spec,
                  row_spec, base_spec, row_spec],
        out_specs=pl.BlockSpec((bq, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, LANES), jnp.float32),
        interpret=interpret,
    )(delta_s, base_s, d_s, delta_t, base_t, d_t)
