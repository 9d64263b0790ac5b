"""Kernel dispatch layer for the query hot path.

This module is the single seam between the paper-level query algebra
(`repro.core.query`) and the hardware kernels (`repro.kernels.*`). Both
stages of Algorithm 1 route through here:

  stage 1 — Equation 1 label intersection:
      ``label_intersect_dispatch`` -> ``kernels.label_intersect.ops``
      (tiled equality-join Pallas kernel on TPU, interpret-mode parity
      fallback off-TPU, searchsorted-merge jnp reference).

  stage 2 — label-seeded bidirectional core relaxation:
      ``CoreRelaxer`` — reference backend keeps the COO scatter-min
      wavefront (``core_relax``, bit-identical to the pre-dispatch
      engine); kernel backends pick one of three routes from the core's
      size, density and ELL width (``CoreRelaxer.mode``, see
      docs/KERNELS.md):

      "dense"   — small dense cores (density >= ISLABEL_DENSE_THRESHOLD
                  and n_core <= dense_cap) relax via the
                  ``minplus_matmul`` kernel against a 0-diagonal dense
                  adjacency: one tropical GEMM per round.
      "fused"   — small sparse cores (``fused_fits``): one
                  ``fused_relax_kernel`` launch runs ALL rounds with
                  both stacked frontiers resident in VMEM and the
                  fixed-point exit inside the kernel.
      "ell_xla" — every larger core: an XLA program, one ELL gather
                  round per ``lax.while_loop`` step over a vertex-major
                  frontier. Mosaic gathers only within one vreg, so no
                  Pallas form of this step lowers at these sizes.

A directed core (paper §8.2) relaxes the target side over the reversed
core: the reference route scatters it over the reversed arcs, and the
kernel routes relax both frontiers side by side, as one row per query,
over the block-diagonal union of the core and its reverse (``directed``
layout of ``_stack``/``_finish``). An undirected core keeps the stacked
rows over the one graph.

Every route computes the same per-round fixed point (synchronous Jacobi
Bellman-Ford over G_k), so answers agree bitwise: each round takes a min
over the identical multiset of candidate sums regardless of whether the
edges are visited scatter-wise (COO), gather-wise (ELL), or as a dense
min-plus product (the 0 diagonal supplies the keep-old term; parallel
edges dedup exactly because fp add is monotone in w). Rows relax
independently, so per-block fixed points freeze bitwise and
``max(block rounds) == loop rounds``.

Query chunking lives one level up (``QueryEngine.query``): the batch is
tiled into fixed-size chunks so a 10k-query batch never materializes a
dense ``[Q, n_core+1]`` frontier per direction in one launch — peak
frontier memory is ``O(query_chunk * n_core)`` instead of
``O(Q * n_core)``.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.labels import LabelRows
from repro.kernels.backend import pallas_interpret, resolve_backend
from repro.kernels.label_intersect import ops as li_ops
from repro.kernels.minplus_matmul.kernel import minplus_matmul_kernel
from repro.kernels.spmv_relax.kernel import (FUSED_VMEM_BUDGET, LANES,
                                             fused_relax_kernel,
                                             fused_vmem_bytes)
from repro.kernels.spmv_relax.ops import coo_to_ell

# Largest padded core the fused kernel takes: its in-vreg gather scans
# every source tile, so a round costs O((V/128)^2 · D) vreg ops. Above
# this the XLA gather round relaxes the core. The crossover between the
# two on a chip is not measured; this matches the dense route's cap.
FUSED_MAX_V = 2048


def fused_fits(vp: int, width: int, bq: int,
               vmem_budget: int = FUSED_VMEM_BUDGET) -> bool:
    """Whether a core padded to ``vp`` vertices with ELL ``width`` takes
    the fused kernel (shared by ``CoreRelaxer`` and version families)."""
    return (vp <= FUSED_MAX_V
            and fused_vmem_bytes(vp, width, bq) <= vmem_budget)


@partial(jax.jit, static_argnames=("n_sentinel", "backend"))
def label_intersect_dispatch(ids_s, d_s, ids_t, d_t, n_sentinel: int,
                             backend: str):
    """Equation 1 μ via the resolved kernel backend. Returns float32[Q]."""
    # named_scope threads through to XLA HLO metadata, so profiler
    # traces (jax.profiler / --profile-dir) attribute device time to
    # the paper's stages (docs/OBSERVABILITY.md)
    with jax.named_scope("islabel.label_intersect"):
        return li_ops.label_intersect(ids_s, d_s, ids_t, d_t, n_sentinel,
                                      backend=backend)


@partial(jax.jit, static_argnames=("n_sentinel", "codec", "backend"))
def label_intersect_rows_dispatch(rows_s: LabelRows, rows_t: LabelRows,
                                  n_sentinel: int, codec: str,
                                  backend: str):
    """Equation 1 μ over gathered ``LabelRows`` in either codec — the
    compressed path fuses decode into the join kernel."""
    with jax.named_scope("islabel.label_intersect"):
        return li_ops.label_intersect_rows(rows_s, rows_t, n_sentinel,
                                           codec=codec, backend=backend)


@partial(jax.jit, static_argnames=("n_core", "max_rounds"))
def core_relax(seed_s, seed_t, ce_src, ce_dst, ce_w, mu,
               n_core: int, max_rounds: int, t_edges=None):
    """Reference bidirectional label-seeded relaxation on G_k (Alg. 1
    stage 2) — COO scatter-min wavefront rounds.

    seed_s/seed_t: [Q, n_core+1] initial distance vectors (+inf default,
    label distances scattered in, sentinel column n_core).
    ``t_edges`` (src, dst, w): the arcs the target side relaxes over
    (the reversed core of a directed index); None: the same arcs.
    Returns (ans [Q], ds, dt, rounds) with ans = min(μ, min_v ds+dt).
    """
    t_src, t_dst, t_w = (ce_src, ce_dst, ce_w) if t_edges is None \
        else t_edges

    def body(state):
        ds, dt, it, _ = state
        cs = ds[:, ce_src] + ce_w[None, :]
        ds2 = ds.at[:, ce_dst].min(cs)
        ct = dt[:, t_src] + t_w[None, :]
        dt2 = dt.at[:, t_dst].min(ct)
        improved = jnp.any(ds2 < ds) | jnp.any(dt2 < dt)
        return ds2, dt2, it + 1, improved

    def cond(state):
        _, _, it, improved = state
        return improved & (it < max_rounds)

    with jax.named_scope("islabel.core_relax"):
        ds, dt, rounds, _ = jax.lax.while_loop(
            cond, body, (seed_s, seed_t, jnp.int32(0), jnp.bool_(True)))
        # the sentinel column n_core parks non-core label entries —
        # exclude it
        through_core = jnp.min(ds[:, :n_core] + dt[:, :n_core], axis=1)
        return jnp.minimum(mu, through_core), ds, dt, rounds


def _stack(seed_s, seed_t, cols_to: int, directed: bool, row_mult: int = 1):
    """Both frontiers in one matrix, +inf padded to ``cols_to`` columns
    and a multiple of ``row_mult`` rows: stacked as rows, [2Q, V], over
    one graph; side by side, [Q, 2V], over a directed core's
    block-diagonal union with its reverse."""
    d0 = jnp.concatenate([seed_s, seed_t], axis=1 if directed else 0)
    rows, v = d0.shape
    rows_to = -(-rows // row_mult) * row_mult
    return jnp.pad(d0, ((0, rows_to - rows), (0, cols_to - v)),
                   constant_values=jnp.inf)


def _finish(d, mu, q: int, v: int, n_core: int, directed: bool):
    """Unstack ``_stack``'s layout (padded) and meet the two frontiers."""
    ds = d[:q, :v]
    dt = d[:q, v:2 * v] if directed else d[q:2 * q, :v]
    through_core = jnp.min(ds[:, :n_core] + dt[:, :n_core], axis=1)
    return jnp.minimum(mu, through_core), ds, dt


def ell_round(d, nbr_ids_t, nbr_w_t):
    """One synchronous ELL relaxation round on a vertex-major [Vp, R]
    frontier: d'[v] = min(d[v], min_j d[nbr[j, v]] + w[j, v]), one row
    gather per slot so nothing of size [Vp, R, D] is materialized."""
    def slot(j, cand):
        return jnp.minimum(cand, d[nbr_ids_t[j]] + nbr_w_t[j][:, None])
    return jax.lax.fori_loop(0, nbr_ids_t.shape[0], slot, d)


@partial(jax.jit, static_argnames=("n_core", "max_rounds", "directed"))
def _core_relax_ell(seed_s, seed_t, nbr_ids, nbr_w, mu, n_core: int,
                    max_rounds: int, directed: bool = False):
    """XLA relaxation: both frontiers stacked vertex-major into one
    [Vp, 2Q] matrix ([2Vp, Q] directed; a relaxation step gathers whole
    rows), one ``ell_round`` per ``lax.while_loop`` step."""
    q, v = seed_s.shape
    vp = nbr_ids.shape[0]
    d0 = _stack(seed_s, seed_t, vp, directed).T
    ids_t, w_t = nbr_ids.T, nbr_w.T

    def body(state):
        d, it, _ = state
        d2 = ell_round(d, ids_t, w_t)
        return d2, it + 1, jnp.any(d2 < d)

    def cond(state):
        _, it, improved = state
        return improved & (it < max_rounds)

    with jax.named_scope("islabel.core_relax_ell"):
        d, rounds, _ = jax.lax.while_loop(
            cond, body, (d0, jnp.int32(0), jnp.bool_(True)))
        return (*_finish(d.T, mu, q, v, n_core, directed), rounds)


@partial(jax.jit, static_argnames=("n_core", "max_rounds", "interpret",
                                   "bq", "directed"))
def _core_relax_fused(seed_s, seed_t, nbr_ids, nbr_w, mu, n_core: int,
                      max_rounds: int, interpret: bool, bq: int,
                      directed: bool = False):
    """Fused relaxation: both frontiers stacked, ALL rounds in one
    ``fused_relax_kernel`` launch with the fixed-point exit in-kernel.
    Batch rounds = max over per-block rounds (all-pad blocks settle in
    one round, real blocks freeze bitwise at their own fixed point)."""
    q, v = seed_s.shape
    vp = nbr_ids.shape[0]
    d0 = _stack(seed_s, seed_t, vp, directed, bq)

    with jax.named_scope("islabel.core_relax_fused"):
        d, blk_rounds = fused_relax_kernel(
            d0, nbr_ids.T, nbr_w.T, max_rounds=max_rounds, bq=bq,
            interpret=interpret)
        rounds = jnp.max(blk_rounds, initial=0).astype(jnp.int32)
        return (*_finish(d, mu, q, v, n_core, directed), rounds)


@partial(jax.jit, static_argnames=("n_core", "max_rounds", "interpret",
                                   "bm", "directed"))
def _core_relax_dense(seed_s, seed_t, adj, mu, n_core: int,
                      max_rounds: int, interpret: bool, bm: int = 8,
                      directed: bool = False):
    """Dense-core relaxation: one ``minplus_matmul`` tropical GEMM per
    round against the 0-diagonal adjacency (the diagonal supplies the
    keep-old term, so ``minplus(d, adj)`` IS the synchronous round)."""
    q, v = seed_s.shape
    vp = adj.shape[0]
    d0 = _stack(seed_s, seed_t, vp, directed, bm)

    def body(state):
        d, it, _ = state
        d2 = minplus_matmul_kernel(d, adj, bm=bm, interpret=interpret)
        return d2, it + 1, jnp.any(d2 < d)

    def cond(state):
        _, it, improved = state
        return improved & (it < max_rounds)

    with jax.named_scope("islabel.core_relax_dense"):
        d, rounds, _ = jax.lax.while_loop(
            cond, body, (d0, jnp.int32(0), jnp.bool_(True)))
        return (*_finish(d, mu, q, v, n_core, directed), rounds)


class CoreRelaxer:
    """Backend-dispatched stage-2 relaxation over the local core graph.

    Holds the COO edge arrays (local indices in [0, n_core), weights)
    and lazily derives the kernel-side layouts: the ELL planes the
    fused kernel and the XLA gather round consume, and (for dense cores) the
    0-diagonal dense adjacency for ``minplus_matmul`` — each built once
    per index on first kernel-path query, padded to lane-aligned vertex
    counts so launches need no reshaping.

    Kernel-route selection (``.mode``) happens at dispatch time:
    density >= ``dense_threshold`` (env ``ISLABEL_DENSE_THRESHOLD``)
    with n_core <= ``dense_cap`` -> "dense"; else "fused" when
    ``fused_fits`` (small core, working set inside the VMEM budget);
    else "ell_xla". Set env ``ISLABEL_FUSED_RELAX=0`` to skip the fused
    kernel.

    ``rev`` (src, dst, w), local indices: the arcs the target side
    relaxes over, the reversed core of a directed index. None (an
    undirected core): both sides relax over the same arcs. With ``rev``
    the kernel layouts are of the block-diagonal union of the core
    (vertices [0, V)) and ``rev`` (vertices [V, 2V)), V = n_core + 1.
    """

    def __init__(self, ce_src, ce_dst, ce_w, n_core: int, *, rev=None,
                 bq: int = 8, d_width: int = 16,
                 fused: bool | None = None,
                 dense_threshold: float | None = None,
                 dense_cap: int = 2048,
                 vmem_budget: int = FUSED_VMEM_BUDGET):
        self.ce_src = ce_src
        self.ce_dst = ce_dst
        self.ce_w = ce_w
        self.n_core = n_core
        self.rev = rev
        self.bq = bq
        self.d_width = d_width
        if fused is None:
            fused = os.environ.get("ISLABEL_FUSED_RELAX", "1") != "0"
        self.fused = fused
        if dense_threshold is None:
            dense_threshold = float(
                os.environ.get("ISLABEL_DENSE_THRESHOLD", "0.05"))
        self.dense_threshold = dense_threshold
        self.dense_cap = dense_cap
        self.vmem_budget = vmem_budget
        self.density = (len(ce_src) / (n_core * n_core)) if n_core else 0.0
        self._ell = None
        self._adj = None
        self._mode = None

    @property
    def mode(self) -> str:
        """Stage-2 route: "dense" | "fused" | "ell_xla" (reference
        backend bypasses this entirely)."""
        if self._mode is None:
            if (0 < self.n_core <= self.dense_cap
                    and self.density >= self.dense_threshold):
                self._mode = "dense"
            elif self.fused:
                nbr_ids, _ = self.ell()
                vp, width = nbr_ids.shape
                fits = fused_fits(vp, width, self.bq, self.vmem_budget)
                self._mode = "fused" if fits else "ell_xla"
            else:
                self._mode = "ell_xla"
        return self._mode

    def _graph(self):
        """(vertex count, src, dst, w) on the host of the graph the
        kernel routes relax over: the core, or with ``rev`` the
        block-diagonal union of the core and ``rev``."""
        v = self.n_core + 1
        src, dst, w = (np.asarray(a) for a in
                       (self.ce_src, self.ce_dst, self.ce_w))
        if self.rev is None:
            return v, src, dst, w
        r_src, r_dst, r_w = (np.asarray(a) for a in self.rev)
        return (2 * v, np.concatenate([src, r_src + v]),
                np.concatenate([dst, r_dst + v]), np.concatenate([w, r_w]))

    def dense_adj(self):
        """[Vp, Vp] float32 dense adjacency: adj[src, dst] = min edge
        weight (parallel edges dedup exactly — fp add is monotone in w),
        +inf elsewhere, diagonal min'd with 0 on ALL rows including the
        sentinel and lane padding so parked values survive each round."""
        if self._adj is None:
            v, src, dst, w = self._graph()
            vp = -(-v // LANES) * LANES
            adj = np.full((vp, vp), np.inf, np.float32)
            if len(src):
                np.minimum.at(adj, (src, dst), np.asarray(w, np.float32))
            idx = np.arange(vp)
            adj[idx, idx] = np.minimum(adj[idx, idx], 0.0)
            # lazily built, possibly first reached inside a jit /
            # shard_map trace — keep the cached array a concrete device
            # constant, never a tracer
            with jax.ensure_compile_time_eval():
                self._adj = jnp.asarray(adj)
        return self._adj

    def ell(self):
        """(nbr_ids [Vp, D], nbr_w [Vp, D]) with Vp = n_core+1 (2·(n_core+1)
        with ``rev``) rounded up to a multiple of 128 (sentinel columns
        included, padding rows edgeless)."""
        if self._ell is None:
            v, src, dst, w = self._graph()
            vp = -(-v // LANES) * LANES
            with jax.ensure_compile_time_eval():
                ids, ws = coo_to_ell(v, src, dst, w, d_width=self.d_width)
                ids = jnp.pad(ids, ((0, vp - v), (0, 0)))
                ws = jnp.pad(ws, ((0, vp - v), (0, 0)),
                             constant_values=jnp.inf)
            self._ell = (ids, ws)
        return self._ell

    def run(self, seed_s, seed_t, mu, max_rounds: int, backend=None):
        """Relax to convergence. Returns (ans, ds, dt, rounds) with
        ds/dt of shape [Q, n_core+1] (matching ``core_relax``)."""
        backend = resolve_backend(backend)
        if backend == "reference":
            return core_relax(seed_s, seed_t, self.ce_src, self.ce_dst,
                              self.ce_w, mu, self.n_core, max_rounds,
                              self.rev)
        interpret = pallas_interpret(backend)
        mode = self.mode
        directed = self.rev is not None
        if mode == "dense":
            return _core_relax_dense(seed_s, seed_t, self.dense_adj(), mu,
                                     self.n_core, max_rounds, interpret,
                                     self.bq, directed)
        nbr_ids, nbr_w = self.ell()
        if mode == "fused":
            return _core_relax_fused(seed_s, seed_t, nbr_ids, nbr_w, mu,
                                     self.n_core, max_rounds, interpret,
                                     self.bq, directed)
        return _core_relax_ell(seed_s, seed_t, nbr_ids, nbr_w, mu,
                               self.n_core, max_rounds, directed)
