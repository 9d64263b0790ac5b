"""Fused ELL min-plus relaxation Pallas kernel — all wavefront rounds
in one launch.

new_dist[q, v] = min(dist[q, v], min_j dist[q, nbr[v, j]] + w[v, j])

This is the inner loop of the label-seeded core search (paper Alg. 1
stage 2) for a batch of queries: the core graph G_k in ELL layout
(fixed-width in-neighbor lists). Each grid step owns a [bq, V] block of
stacked query frontiers; the block, the ELL planes, and the round loop
live entirely in VMEM, with the fixed-point early exit
(``improved & it < max_rounds``) inside the kernel. Per-block round
counts come out as a second output; their max equals the global round
count (rows relax independently, so a block at its fixed point stays
bitwise-frozen through extra rounds elsewhere). Compulsory HBM traffic
is O(Q·V) for the whole search — see docs/KERNELS.md.

The gather. Mosaic gathers only inside one (8, 128) vreg
(``tpu.dynamic_gather``: "Multiple source vregs along gather dimension"
is refused). So for every 128-vertex output tile and ELL slot, the
kernel scans the V/128 source tiles of the frontier, gathers within
each by the neighbour's lane (``id & 127``), and keeps the lanes whose
neighbour lives in that tile (``id >> 7``). Work per round is
O((V/128)^2 · D) vreg ops, so the dispatcher admits this kernel only for
small cores (``core.dispatch.FUSED_MAX_V``); larger cores relax in an
XLA program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# Scoped-VMEM limit the kernel compiles with (v5e default: 16 MiB), and
# the budget ``fused_vmem_bytes`` must fit for the dispatcher to pick
# it. tests/test_tpu_compile.py compiles at the largest shape this
# admits and sees the compiler refuse the next one.
FUSED_VMEM_BUDGET = 32 * 2 ** 20


def _fused_kernel(dist_ref, nbr_ref, w_ref, o_ref, rounds_ref, cur_ref, *,
                  max_rounds):
    bq, vp = dist_ref.shape
    width = nbr_ref.shape[0]
    n_tiles = vp // LANES

    def tile(ref, t):
        return ref[:, pl.ds(pl.multiple_of(t * LANES, LANES), LANES)]

    def relax_tile(t, carry):
        off = pl.multiple_of(t * LANES, LANES)

        def scan_source(c, acc):
            src = tile(cur_ref, c)                        # [bq, 128]

            def slot_group(g, acc):
                rows = pl.ds(pl.multiple_of(g * SUBLANES, SUBLANES),
                             SUBLANES)
                ids = nbr_ref[rows, pl.ds(off, LANES)]    # [8, 128]
                w = w_ref[rows, pl.ds(off, LANES)]
                for j in range(SUBLANES):
                    idx = jnp.broadcast_to(ids[j:j + 1, :], (bq, LANES))
                    got = jnp.take_along_axis(src, idx & (LANES - 1),
                                              axis=1)
                    here = (idx >> 7) == c
                    acc = jnp.minimum(
                        acc, jnp.where(here, got + w[j:j + 1, :], jnp.inf))
                return acc

            return jax.lax.fori_loop(0, width // SUBLANES, slot_group, acc)

        # Jacobi round: candidates read cur (the previous round) and the
        # result goes to o_ref, so every route sees identical operands
        o_ref[:, pl.ds(off, LANES)] = jax.lax.fori_loop(
            0, n_tiles, scan_source, tile(cur_ref, t))
        return carry

    def commit_tile(t, changed):
        off = pl.multiple_of(t * LANES, LANES)
        new = o_ref[:, pl.ds(off, LANES)]
        old = cur_ref[:, pl.ds(off, LANES)]
        cur_ref[:, pl.ds(off, LANES)] = new
        return jnp.maximum(changed, jnp.where(new < old, 1, 0))

    def round_(state):
        it, _ = state
        jax.lax.fori_loop(0, n_tiles, relax_tile, 0)
        changed = jax.lax.fori_loop(0, n_tiles, commit_tile,
                                    jnp.zeros((bq, LANES), jnp.int32))
        return it + 1, jnp.max(changed) > 0

    def cond(state):
        it, improved = state
        return improved & (it < max_rounds)

    cur_ref[...] = dist_ref[...]
    o_ref[...] = dist_ref[...]
    it, _ = jax.lax.while_loop(cond, round_, (jnp.int32(0), jnp.bool_(True)))
    rounds_ref[...] = jnp.full(rounds_ref.shape, it, jnp.int32)


def fused_vmem_bytes(v: int, d_width: int, bq: int = 8) -> int:
    """Scoped VMEM the TPU compiler allocates for the fused kernel: the
    [bq, V] frontier in and out blocks (double-buffered by the
    pipeline) and the round-carry scratch; the two [D, V] ELL planes,
    whose block never moves and is buffered once; and the [bq, 128]
    rounds block, double-buffered. This is the compiler's own figure
    (its out-of-VMEM error reports the same sum), compared with
    ``FUSED_VMEM_BUDGET``."""
    return 4 * (5 * bq * v + 2 * d_width * v + 2 * bq * LANES)


@functools.partial(jax.jit,
                   static_argnames=("max_rounds", "bq", "interpret"))
def fused_relax_kernel(dist, nbr_ids_t, nbr_w_t, *, max_rounds: int, bq=8,
                       interpret=False):
    """All relaxation rounds in one launch. dist: [Q, V] f32 seeds
    (Q % bq == 0, V % 128 == 0); nbr_ids_t/nbr_w_t: [D, V] ELL planes,
    slot-major (D % 8 == 0). Returns (fixed-point dist [Q, V],
    per-block rounds int32[Q, 128] — every entry of a block's rows holds
    that block's count; ``max`` is the batch's round count,
    bitwise-identical to the per-round loop's)."""
    q, v = dist.shape
    d, v2 = nbr_ids_t.shape
    assert v == v2 and q % bq == 0 and v % LANES == 0 and d % SUBLANES == 0
    kern = functools.partial(_fused_kernel, max_rounds=max_rounds)
    return pl.pallas_call(
        kern,
        grid=(q // bq,),
        in_specs=[
            pl.BlockSpec((bq, v), lambda i: (i, 0)),
            pl.BlockSpec((d, v), lambda i: (0, 0)),
            pl.BlockSpec((d, v), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, v), lambda i: (i, 0)),
            pl.BlockSpec((bq, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, v), jnp.float32),
            jax.ShapeDtypeStruct((q, LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_BUDGET),
        interpret=interpret,
    )(dist, nbr_ids_t, nbr_w_t)
