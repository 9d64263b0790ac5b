"""Directed-graph IS-LABEL (paper §8.2).

Same vertex hierarchy (independence ignores direction) but distance
preservation creates an augmenting edge (u, w) only for directed 2-paths
u -> v -> w through a removed v. Two label families per vertex:
*out-labels* over out-ancestors (edges low->high level) and *in-labels*
over in-ancestors; a query (s, t) intersects out(s) with in(t) and the
core search relaxes forward from s-seeds and backward from t-seeds.

Implementation: the undirected build and query paths with a direction.
The peel is ``build_hierarchy_device`` with ``directed=True`` (one
``_peel_step`` program, one blocking read per level), which records the
out- and in-adjacency up-edges; the in-label machinery is exactly the
out-label machinery on the reversed up-edges, so ``build_labels`` runs
once per family; queries go through ``QueryEngine`` with the in-labels
as the target side's planes and the reversed core for its stage 2. This
module also answers *reachability* (dist < inf), the paper's closing
claim.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import BuildStats, IndexConfig
from repro.core.hierarchy import build_hierarchy_device
from repro.core.index import build_stats, core_positions, timed_build
from repro.core.labeling import build_labels
from repro.core.query import QueryEngine
from repro.obs.profiler import span


@dataclasses.dataclass
class DiISLabelIndex:
    n: int
    k: int
    cfg: IndexConfig
    level: np.ndarray
    out_lbl: tuple      # (ids, d, pred) device arrays (out-ancestors)
    in_lbl: tuple
    core_pos: np.ndarray
    n_core: int
    engine: QueryEngine
    stats: BuildStats
    # host state for §8.1/§8.2 path reconstruction: the out/in
    # up-adjacency matrices ((ids, w, via) triples) and the core COO in
    # global ids with its via bookkeeping
    up_out: tuple = None
    up_in: tuple = None
    core_host: tuple = None     # (src, dst, w, via) global ids
    # lazy per-call-cost hoists (host label copies, sorted core
    # adjacencies) — the directed index has no in-place mutators, so
    # these never need invalidation
    _host_lbl: dict = dataclasses.field(default=None, init=False,
                                        repr=False, compare=False)
    _core_adj: dict = dataclasses.field(default=None, init=False,
                                        repr=False, compare=False)

    @staticmethod
    def build(n, src, dst, w, cfg: IndexConfig = IndexConfig()):
        """Peel, label both families, assemble: the arcs ``src -> dst``.

        The spans are ``ISLabelIndex.build``'s (``timed_build``), with
        ``islabel.build.label.out`` and ``islabel.build.label.in`` inside
        ``islabel.build.label``, one per ``build_labels`` call; each ends
        in its blocking overflow read. The peel always runs the device
        level loop (``IndexConfig.builder`` picks between the undirected
        loops only)."""
        def label(hier):
            up_in = dataclasses.replace(hier, up_ids=hier.up_in[0],
                                        up_w=hier.up_in[1],
                                        up_via=hier.up_in[2])
            lbls = []
            for family, h in (("out", hier), ("in", up_in)):
                with span(f"islabel.build.label.{family}"):
                    lbls.append(build_labels(h, cfg))
                    jax.block_until_ready(lbls[-1][0])
            return lbls

        return timed_build(
            lambda: build_hierarchy_device(n, src, dst, w, cfg,
                                           directed=True),
            label,
            lambda hier, lbl: DiISLabelIndex._assemble(n, hier, *lbl, cfg,
                                                       m_input=len(src)))

    @staticmethod
    def _assemble(n, hier, out_lbl, in_lbl, cfg: IndexConfig,
                  m_input: int) -> "DiISLabelIndex":
        core_ids, core_pos = core_positions(n, hier)
        n_core = len(core_ids)
        ce_s, ce_d = (jnp.asarray(core_pos[a])
                      for a in (hier.core_src, hier.core_dst))
        ce_w = jnp.asarray(hier.core_w, jnp.float32)
        engine = QueryEngine(
            out_lbl[0], out_lbl[1], jnp.asarray(core_pos), (ce_s, ce_d, ce_w),
            n=n, n_core=n_core, max_rounds=cfg.max_relax_rounds,
            backend=cfg.query_backend, query_chunk=cfg.query_chunk,
            label_dtype=cfg.label_dtype, t_labels=in_lbl[:2],
            t_core_edges=(ce_d, ce_s, ce_w))
        stats = build_stats(n, m_input, hier, cfg,
                            [(np.asarray(out_lbl[0]), hier.up_ids),
                             (np.asarray(in_lbl[0]), hier.up_in[0])])
        return DiISLabelIndex(
            n=n, k=hier.k, cfg=cfg, level=hier.level, out_lbl=out_lbl,
            in_lbl=in_lbl, core_pos=core_pos, n_core=n_core, engine=engine,
            stats=stats, up_out=(hier.up_ids, hier.up_w, hier.up_via),
            up_in=hier.up_in,
            core_host=(hier.core_src, hier.core_dst, hier.core_w,
                       hier.core_via))

    def query(self, s, t):
        """Directed distances dist(s -> t), batched."""
        return self.engine.query(s, t)

    def query_host(self, s, t):
        return np.asarray(self.query(np.atleast_1d(s), np.atleast_1d(t)))

    def reachable(self, s, t):
        return np.isfinite(self.query_host(s, t))

    # ------------------------------------------------------- §8.1/§8.2 paths
    def _label_host(self, family: str):
        """Cached host copies of one label family's (ids, d, pred)."""
        if self._host_lbl is None:
            self._host_lbl = {}
        if family not in self._host_lbl:
            lbl = self.out_lbl if family == "out" else self.in_lbl
            self._host_lbl[family] = tuple(np.asarray(a) for a in lbl)
        return self._host_lbl[family]

    def _core_adjacency(self, reverse: bool = False):
        """Cached src-sorted core adjacency, forward or reversed."""
        if self._core_adj is None:
            self._core_adj = {}
        if reverse not in self._core_adj:
            from repro.core.ref import sorted_adjacency
            ce_s, ce_d, ce_w, ce_v = self.core_host
            src, dst = (ce_d, ce_s) if reverse else (ce_s, ce_d)
            self._core_adj[reverse] = sorted_adjacency(self.n, src, dst,
                                                       ce_w, ce_v)
        return self._core_adj[reverse]

    # Directed via expansion: an augmenting edge (a, b) through a
    # removed c stands for the 2-path a -> c -> b, so a sits in c's
    # *in*-adjacency and b in its *out*-adjacency.
    def _expand_dir(self, a: int, b: int, via: int) -> list[int]:
        """Original-graph vertices [a..b) of the directed edge a -> b."""
        if via < 0:
            return [a]
        sa = self._slot(self.up_in, via, a)
        sb = self._slot(self.up_out, via, b)
        if sa < 0 or sb < 0:
            return [a]
        return (self._expand_dir(a, via, int(self.up_in[2][via, sa]))
                + self._expand_dir(via, b, int(self.up_out[2][via, sb])))

    @staticmethod
    def _slot(up, v: int, u: int) -> int:
        slots = np.flatnonzero(up[0][v] == u)
        return int(slots[0]) if len(slots) else -1

    def _chase(self, v: int, x: int, family: str) -> list[int]:
        """Real-graph vertices of the label path between v and x.

        ``family="out"``: returns [v..x) of the path v -> x (chasing
        out-labels forward). ``family="in"``: returns [x..v) of the
        path x -> v (every in-label hop is a real edge INTO v).
        """
        if v == x:
            return []
        lbl = self._label_host(family)
        up = self.up_out if family == "out" else self.up_in
        row = lbl[0][v]
        j = int(np.searchsorted(row, x))
        if j >= len(row) or row[j] != x:
            raise ValueError(f"{x} is not a {family}-ancestor of {v}")
        u = int(lbl[2][v][j])
        slot = self._slot(up, v, u)
        if u < 0 or slot < 0:
            raise ValueError("inconsistent pred chain")
        via = int(up[2][v, slot])
        if family == "out":
            return self._expand_dir(v, u, via) + self._chase(u, x, "out")
        return self._chase(u, x, "in") + self._expand_dir(u, v, via)

    def shortest_path(self, s: int, t: int):
        """Return (dist(s -> t), [s..t] vertex list in the original
        directed graph) — the directed analogue of
        ``ISLabelIndex.shortest_path``."""
        dist = float(self.query_host([s], [t])[0])
        if not np.isfinite(dist):
            return dist, []
        from repro.core.ref import host_meet
        out_h, in_h = self._label_host("out"), self._label_host("in")
        mu, w = host_meet(out_h[0][s], out_h[1][s], in_h[0][t], in_h[1][t],
                          self.n)
        if mu <= dist + 1e-6 and w >= 0:
            return dist, (self._chase(s, w, "out")
                          + self._chase(t, w, "in") + [t])
        return dist, self._core_path_dir(s, t)

    def _core_path_dir(self, s: int, t: int) -> list[int]:
        from repro.core.ref import seeded_sssp

        def seeds(family, v):
            lbl = self._label_host(family)
            row_i, row_d = lbl[0][v], lbl[1][v]
            return {int(u): float(d) for u, d in zip(row_i, row_d)
                    if int(u) < self.n and self.level[int(u)] == self.k}

        ds, ps = seeded_sssp(seeds("out", s),
                             *self._core_adjacency(reverse=False))
        dt, pt = seeded_sssp(seeds("in", t),
                             *self._core_adjacency(reverse=True))
        meet = min((ds.get(u, np.inf) + dt.get(u, np.inf), u)
                   for u in ds)[1]
        # forward side: unwind par edges (u -> v) back to the s seed
        fwd, v = [], meet
        while ps[v][0] is not None:
            u, via = ps[v]
            fwd = self._expand_dir(u, v, via) + fwd
            v = u
        left = self._chase(s, v, "out") + fwd
        # backward side: par edges are real (v -> u), already forward
        bwd, v = [], meet
        while pt[v][0] is not None:
            u, via = pt[v]
            bwd = bwd + self._expand_dir(v, u, via)
            v = u
        return left + bwd + self._chase(t, v, "in") + [t]
