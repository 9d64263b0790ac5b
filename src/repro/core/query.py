"""Batched P2P distance query engine (paper §4.3, §5.2, Algorithm 1).

Two stages, exactly the paper's:
  1. label intersection -> upper bound μ (Equation 1); exact and final
     for queries whose shortest path never enters the core G_k.
  2. label-seeded core search: the paper's bidirectional Dijkstra on G_k
     becomes *batched bidirectional Bellman-Ford*: both frontiers' dist
     vectors over the core are relaxed each round; loop exits when no
     entry in the batch improves (exact convergence — same fixed point
     Dijkstra reaches). answer = min(μ, min_v DS[v] + DT[v]).

Priority queues do not vectorize; synchronous wavefront relaxation is
the standard data-parallel SSSP formulation and serves thousands of
queries per launch. μ still prunes: converged queries stop contributing
improvements, and the final min with μ implements Line 19.

Both stages execute through the kernel dispatch layer
(``repro.core.dispatch``): stage 1 via the equality-join Pallas
label-intersect kernel (jnp searchsorted reference off-TPU), stage 2 via
the route ``CoreRelaxer.mode`` picks — the dense or fused Pallas kernel
for small cores, an XLA ELL gather round for large ones (COO scatter
reference off-TPU).
``query_chunk`` tiles large batches so the dense per-direction frontier
is ``[chunk, n_core+1]``, never ``[Q, n_core+1]``.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import (CoreRelaxer, core_relax,
                                 label_intersect_rows_dispatch)
from repro.core.labels import (LabelRows, decode_rows, encode_labels,
                               try_encode_labels)
from repro.kernels.backend import resolve_backend

__all__ = ["QueryEngine", "label_intersect_mu", "core_relax"]


@partial(jax.jit, static_argnames=("l_cap",))
def label_intersect_mu(ids_s, d_s, ids_t, d_t, n: int, l_cap: int):
    """Equation 1 over sorted label rows: μ[q] = min_{w∈X} d(s,w)+d(w,t).

    Also returns the meeting ancestor (global id; n if none) — used for
    path reconstruction and Type classification. The serving hot path
    goes through ``dispatch.label_intersect_dispatch`` instead (the
    kernel returns μ only); this stays the oracle for paths/updates.
    """
    del l_cap
    pos = jax.vmap(jnp.searchsorted)(ids_t, ids_s)          # [Q, L]
    pos_c = jnp.minimum(pos, ids_t.shape[1] - 1)
    hit = (jnp.take_along_axis(ids_t, pos_c, 1) == ids_s) & (ids_s < n)
    tot = jnp.where(hit, d_s + jnp.take_along_axis(d_t, pos_c, 1), jnp.inf)
    j = jnp.argmin(tot, axis=1)
    mu = jnp.take_along_axis(tot, j[:, None], 1)[:, 0]
    meet = jnp.where(jnp.isfinite(mu),
                     jnp.take_along_axis(ids_s, j[:, None], 1)[:, 0], n)
    return mu, meet


class QueryEngine:
    """Holds the device-resident index state and compiled query fns.

    ``backend`` selects the kernel execution path ("auto" resolves to
    Pallas on TPU, jnp reference elsewhere; see ``repro.kernels.backend``).
    ``query_chunk`` > 0 tiles query batches into fixed-size chunks.
    ``label_dtype`` ("fp32" | "compressed" | "auto") selects the label
    storage codec (``repro.core.labels``): "compressed" encodes delta16
    ids (+ int32 distances when integral) and raises if the planes don't
    fit; "auto" compresses when possible and silently keeps fp32
    otherwise. Serving gathers the compressed planes directly; decode is
    fused into the intersect kernel and the stage-2 seed scatter.

    A directed index (paper §8.2) gives the target side its own label
    planes, ``t_labels`` = (in-label ids, d), and the reversed core,
    ``t_core_edges`` (local src, dst, w), which stage 2 relaxes the
    t-seeds over. None (undirected): the source side's planes and core
    serve both sides.
    """

    def __init__(self, lbl_ids, lbl_d, core_pos, core_local_edges, n: int,
                 n_core: int, max_rounds: int = 0, backend: str = "auto",
                 query_chunk: int = 0, label_dtype: str = "fp32",
                 t_labels=None, t_core_edges=None):
        self.lbl_ids = lbl_ids
        self.lbl_d = lbl_d
        self.core_pos = core_pos              # int32[n+1] -> [0..n_core]
        self.ce_src, self.ce_dst, self.ce_w = core_local_edges
        self.n = n
        self.n_core = n_core
        self.l_cap = lbl_ids.shape[1]
        self.max_rounds = max_rounds if max_rounds > 0 else max(n_core, 1)
        self.backend = backend
        self.query_chunk = query_chunk
        if label_dtype not in ("fp32", "compressed", "auto"):
            raise ValueError(f"unknown label_dtype {label_dtype!r}")
        self.label_dtype = label_dtype
        self.codec = "none"
        planes = [(lbl_ids, None, lbl_d)]
        if t_labels is not None:
            planes.append((t_labels[0], None, t_labels[1]))
        if label_dtype != "fp32":
            encode = (encode_labels if label_dtype == "compressed"
                      else try_encode_labels)
            encs = [encode(np.asarray(ids), np.asarray(d), n)
                    for ids, _, d in planes]
            if all(enc is not None for enc in encs):
                self.codec = "delta16"
                planes = [tuple(jnp.asarray(a) for a in enc) for enc in encs]
        # (ids, base, d) of the source side, then of the target side
        self.planes = (planes[0], planes[-1])
        self.enc_ids, self.enc_base, self.enc_d = planes[0]
        self.relaxer = CoreRelaxer(self.ce_src, self.ce_dst, self.ce_w,
                                   n_core, rev=t_core_edges) \
            if n_core > 0 else None
        self._last_rounds = 0
        self._batch_fns: dict = {}     # backend -> jitted serving callable
        self._mu_batch_fns: dict = {}

    def _rows(self, idx, side: int = 0) -> LabelRows:
        """Gather label rows for a vertex batch in the active codec, from
        the source (``side`` 0) or the target (1) planes."""
        ids, base, d = self.planes[side]
        return LabelRows(ids[idx], None if base is None else base[idx],
                         d[idx])

    def _seed(self, ids, d):
        q = ids.shape[0]
        cpos = self.core_pos[jnp.minimum(ids, self.n)]       # [Q, L]
        seed = jnp.full((q, self.n_core + 1), jnp.inf, jnp.float32)
        ridx = jnp.broadcast_to(jnp.arange(q)[:, None], cpos.shape)
        return seed.at[ridx, cpos].min(jnp.where(ids < self.n, d, jnp.inf))

    def _query_block(self, s, t, backend: str):
        """One fixed-size block through both stages. Returns (ans,
        rounds) with rounds a device scalar (None when there is no
        core) — callers reduce it lazily so chunked batches never sync
        to host between launches."""
        rows_s, rows_t = self._rows(s), self._rows(t, 1)
        mu = label_intersect_rows_dispatch(rows_s, rows_t, self.n,
                                           self.codec, backend)
        if self.n_core == 0:
            return mu, None
        ids_s, d_s = decode_rows(rows_s, self.n, self.codec)
        ids_t, d_t = decode_rows(rows_t, self.n, self.codec)
        seed_s = self._seed(ids_s, d_s)
        seed_t = self._seed(ids_t, d_t)
        ans, _, _, rounds = self.relaxer.run(seed_s, seed_t, mu,
                                             self.max_rounds, backend)
        return ans, rounds

    def query(self, s, t, backend: str | None = None,
              query_chunk: int | None = None):
        """Batched distances. s, t: int32[Q] device/host arrays."""
        s = jnp.asarray(s, jnp.int32)
        t = jnp.asarray(t, jnp.int32)
        backend = resolve_backend(self.backend if backend is None else backend)
        chunk = self.query_chunk if query_chunk is None else query_chunk
        q = s.shape[0]
        if chunk <= 0 or chunk >= q:
            ans, rounds = self._query_block(s, t, backend)
            self._last_rounds = 0 if rounds is None else int(rounds)
            return ans
        outs, rounds_all = [], []
        for start in range(0, q, chunk):
            size = min(chunk, q - start)
            sb, tb = s[start:start + size], t[start:start + size]
            if size < chunk:          # fixed shapes: no per-tail recompile
                sb = jnp.pad(sb, (0, chunk - size), mode="edge")
                tb = jnp.pad(tb, (0, chunk - size), mode="edge")
            ans, rounds = self._query_block(sb, tb, backend)
            outs.append(ans[:size])
            if rounds is not None:
                rounds_all.append(rounds)
        out = jnp.concatenate(outs)
        self._last_rounds = max((int(r) for r in rounds_all), default=0)
        return out

    def query_mu_only(self, s, t, backend: str | None = None):
        """Equation-1-only answers (exact for §5.2 Type-1 queries)."""
        s = jnp.asarray(s, jnp.int32)
        t = jnp.asarray(t, jnp.int32)
        backend = resolve_backend(self.backend if backend is None else backend)
        return label_intersect_rows_dispatch(self._rows(s), self._rows(t, 1),
                                             self.n, self.codec, backend)

    def classify(self, s, t, level, k):
        """Paper Table 5 endpoint classes: 1 = both core, 2 = one core,
        3 = neither. Accepts host or device arrays (and scalars) for
        every argument; always returns a host int array."""
        s = np.atleast_1d(np.asarray(s, np.int64))
        t = np.atleast_1d(np.asarray(t, np.int64))
        level = np.asarray(level)
        in_core = (level[s] == k).astype(np.int32) + \
                  (level[t] == k).astype(np.int32)
        return 3 - in_core

    # ------------------------------------------------------- serving APIs
    def batch_fn(self, backend: str | None = None):
        """Jitted fixed-shape batched query callable for serving.

        Returns ``run(s, t) -> (ans float32[Q], rounds int32 scalar)``
        with no host sync inside — the serving layer owns blocking and
        timing. One compilation per distinct batch shape; the returned
        object is memoized per resolved backend on this engine (shared
        by every server over the index), so its jit cache counts the
        engine's compiled shapes — serving must never grow them after
        warmup.
        """
        backend = resolve_backend(self.backend if backend is None else backend)
        if backend not in self._batch_fns:
            def run(s, t):
                ans, rounds = self._query_block(s, t, backend)
                return ans, (jnp.int32(0) if rounds is None else rounds)
            self._batch_fns[backend] = jax.jit(run)
        return self._batch_fns[backend]

    def mu_batch_fn(self, backend: str | None = None):
        """Jitted fixed-shape Equation-1-only callable (Type-1 fast
        path): ``run(s, t) -> ans float32[Q]``. Memoized per backend,
        same contract as ``batch_fn``."""
        backend = resolve_backend(self.backend if backend is None else backend)
        if backend not in self._mu_batch_fns:
            def run(s, t):
                return label_intersect_rows_dispatch(
                    self._rows(s), self._rows(t, 1), self.n, self.codec,
                    backend)
            self._mu_batch_fns[backend] = jax.jit(run)
        return self._mu_batch_fns[backend]

    def warmup(self, batch_sizes, backend: str | None = None,
               mu_only: bool = False) -> dict:
        """Pre-compile the serving entry points for every batch size.

        Runs one dummy batch per (path, size) through ``batch_fn`` /
        ``mu_batch_fn`` so no XLA compile happens on the serving path.
        Returns {(path, size): seconds} compile+run timings.
        """
        fns = [("mu", self.mu_batch_fn(backend))]
        if not mu_only:
            fns.append(("full", self.batch_fn(backend)))
        out = {}
        for name, fn in fns:
            for size in batch_sizes:
                z = jnp.zeros(int(size), jnp.int32)
                t0 = time.perf_counter()
                jax.block_until_ready(fn(z, z))
                out[(name, int(size))] = time.perf_counter() - t0
        return out
