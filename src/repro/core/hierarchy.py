"""Vertex-hierarchy construction (paper §4.1, §5.1; Algorithms 2+3).

Each level: pick an independent set L_i of G_i (mis.py), record the
adjacency of L_i at removal time (``ADJ(L_i)`` — these become the
*up-edges* used for labeling and path reconstruction), then rebuild the
edge list: surviving edges + augmenting edges (u,w) for every 2-path
u-v-w through a removed v, deduped keeping min weight (Alg. 3's external
sort-merge, expressed as lexsort + segment_min).

Two builders share the level loop semantics (docs/CONSTRUCTION.md):

``build_hierarchy_device`` (default) keeps every buffer device-resident
across levels: level assignment and up-edge recording happen inside the
jitted ``_peel_step`` (donated buffers, masked ``where`` under the IS
mask), and the only blocking host transfer per level is one int32[6]
stat vector — IS size, deduped edge count, augmentation fill, MIS
rounds, next graph size, next eligible-degree bound — from which the
host applies the stop rule and the overflow checks (the overflow flags
ride the same transfer, so the check costs no extra sync and still
raises with the offending level) and sizes the next level's
augmentation buffer.
Level/up-edge/core arrays come back to host in one final pull.

A directed graph (paper §8.2) runs the same step under the static
``directed`` flag: the MIS runs on the symmetrised view (independence
ignores direction), an augmenting arc (p, u) is made for every 2-path
p -> v -> u through a removed v (IN(v) x OUT(v)), and both v's out- and
in-adjacency are recorded as up-edges. The undirected program is
unchanged by the flag.

``build_hierarchy_host`` is the original loop — one ``peel_level`` call
per level with per-level scalar syncs and full neighbor-matrix round
trips through numpy. It is kept as the reference the construction bench
gates the device builder against, bitwise, at fixed seed.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sync as hsync
from repro.core.config import IndexConfig
from repro.core.mis import independent_set
from repro.graphs import csr as gcsr
from repro.graphs import segment_ops as sops
from repro.obs.profiler import span


@dataclasses.dataclass
class Hierarchy:
    """Host-side result of the peeling loop."""
    n: int
    k: int                      # level of the core (vertices in G_k)
    level: np.ndarray           # int32[n], 1..k
    # up-edges: for every non-core v, its adjacency in G_{level(v)}
    up_ids: np.ndarray          # int32[n+1, d_cap], sentinel n
    up_w: np.ndarray            # float32[n+1, d_cap], inf pad
    up_via: np.ndarray          # int32[n+1, d_cap], -1 = original edge
    # core graph (G_k) in *global* vertex ids
    core_src: np.ndarray
    core_dst: np.ndarray
    core_w: np.ndarray
    core_via: np.ndarray
    level_sizes: list
    graph_sizes: list
    mis_rounds: list
    # a directed build's in-adjacency up-edges (ids, w, via), laid out
    # as up_*; up_* then hold the out-adjacency. None when undirected.
    up_in: tuple | None = None
    host_syncs: int = 0         # blocking device→host reads in the level loop
    peel_iters: int = 0         # level-loop iterations (peel_level calls) —
                                # the bench gate is host_syncs <= peel_iters
    # work against padding, per level-loop iteration (read with the
    # per-level stats; BuildStats sums them)
    e_cap: int = 0              # edge-buffer rows every iteration runs over
    # augmentation-buffer rows of each iteration (``level_aug_cap``)
    aug_caps: list = dataclasses.field(default_factory=list)
    edges: list = dataclasses.field(default_factory=list)     # n_unique
    is_edges: list = dataclasses.field(default_factory=list)  # n_is_edges


# The augmentation buffer holds the IS-incident edges (v, u), v in L_i.
# L_i lies inside the eligible set (active, degree <= d_cap; mis.py), so
# their count never exceeds B = the out-degree sum over eligible
# vertices (degree = in + out when directed). Each level's buffer is B
# rounded up to a power of two, so a level compiles one of few shapes,
# under the ceiling ``cfg.aug_cap(m0)``, the only cap that can overflow.

def level_aug_cap(bound: int, ceiling: int) -> int:
    """Augmentation-buffer rows for a level whose eligible-degree bound
    is ``bound``: ``min(ceiling, max(64, next_pow2(bound)))``."""
    return min(ceiling, max(64, 1 << max(int(bound) - 1, 0).bit_length()))


def eligible_degree_bound_host(n: int, src, dst, d_cap: int,
                               directed: bool = False) -> int:
    """B over the input edge list, on the host (every vertex active)."""
    out_deg = np.bincount(np.asarray(src), minlength=n)[:n]
    deg = out_deg
    if directed:
        deg = deg + np.bincount(np.asarray(dst), minlength=n)[:n]
    return int(out_deg[deg <= d_cap].sum())


def _eligible_degree_bound(src, dst, active, n: int, d_cap: int,
                           directed: bool):
    """B over a sentinel-padded device edge list (traced int32)."""
    valid = src < n
    out_deg = sops.count_per_segment(src, n + 1, mask=valid)[:n]
    deg = out_deg
    if directed:
        deg = deg + sops.count_per_segment(dst, n + 1, mask=valid)[:n]
    return jnp.sum(jnp.where(active & (deg <= d_cap), out_deg, 0))


@partial(jax.jit, static_argnames=("n", "d_cap", "aug_cap", "directed"))
def peel_level(src, dst, w, via, active, rng, n: int, d_cap: int, aug_cap: int,
               directed: bool = False):
    """One hierarchy level. Returns the new edge list + bookkeeping.

    All arrays fixed-shape; counters returned for host-side overflow
    checks. e_cap is implied by src.shape. ``nbrs`` holds one
    ``(ids, w, via)`` neighbour matrix per up-edge family: the adjacency
    (undirected), or the out- then the in-adjacency (``directed``).
    """
    e_cap = src.shape[0]
    valid = src < n
    if directed:
        # independence ignores direction: the MIS runs on the
        # symmetrised view (degree = in + out)
        in_is, rounds = independent_set(
            jnp.concatenate([src, dst]), jnp.concatenate([dst, src]),
            jnp.concatenate([valid, valid]), active, rng, n, d_cap)
    else:
        in_is, rounds = independent_set(src, dst, valid, active, rng, n,
                                        d_cap)

    # --- ADJ(L_i): neighbor matrix rows of IS vertices --------------------
    nbr_ids, nbr_w, nbr_via, _ = gcsr.neighbor_matrix(
        gcsr.EdgeList(src, dst, w, via, n_nodes=n), d_cap)
    nbrs = ((nbr_ids, nbr_w, nbr_via),)
    # partners of an IS vertex v: its neighbours, or its in-neighbours
    part_ids, part_w = nbr_ids, nbr_w
    if directed:
        in_ids, in_w, in_via, _ = gcsr.neighbor_matrix(
            gcsr.EdgeList(dst, src, w, via, n_nodes=n), d_cap)
        nbrs += ((in_ids, in_w, in_via),)
        part_ids, part_w = in_ids, in_w

    # --- compact IS-incident edges into the augmentation buffer -----------
    is_src = in_is[jnp.where(valid, src, 0)] & valid   # edge (v,u), v in L_i
    pos = jnp.cumsum(is_src.astype(jnp.int32)) - 1
    tgt = jnp.where(is_src & (pos < aug_cap), pos, aug_cap)
    a_v = jnp.full((aug_cap + 1,), n, jnp.int32).at[tgt].set(
        jnp.where(is_src, src, n), mode="drop")[:aug_cap]
    a_u = jnp.full((aug_cap + 1,), n, jnp.int32).at[tgt].set(
        jnp.where(is_src, dst, n), mode="drop")[:aug_cap]
    a_w = jnp.full((aug_cap + 1,), jnp.inf, jnp.float32).at[tgt].set(
        jnp.where(is_src, w, jnp.inf), mode="drop")[:aug_cap]
    n_is_edges = jnp.sum(is_src.astype(jnp.int32))

    # --- augmenting pairs: (u, partner) for each partner slot of v --------
    # a_* rows: edge (v, u); partners = nbr rows of v. Directed: the
    # partner p is an in-neighbour and the pair is the arc p -> u.
    p_ids = part_ids[a_v]                   # [aug_cap, d_cap]
    p_w = part_w[a_v]
    pair_ok = (p_ids < n) & (p_ids != a_u[:, None]) & (a_u[:, None] < n)
    u_b = jnp.broadcast_to(a_u[:, None], p_ids.shape)
    head, tail = (p_ids, u_b) if directed else (u_b, p_ids)
    pair_src = jnp.where(pair_ok, head, n)
    pair_dst = jnp.where(pair_ok, tail, n)
    pair_w = jnp.where(pair_ok, a_w[:, None] + p_w, jnp.inf)
    pair_via = jnp.where(pair_ok, jnp.broadcast_to(a_v[:, None], p_ids.shape), -1)

    # --- surviving edges ---------------------------------------------------
    drop = in_is[jnp.where(valid, src, 0)] | in_is[jnp.where(valid, dst, 0)]
    keep = valid & ~drop
    k_src = jnp.where(keep, src, n)
    k_dst = jnp.where(keep, dst, n)
    k_w = jnp.where(keep, w, jnp.inf)
    k_via = jnp.where(keep, via, -1)

    all_src = jnp.concatenate([k_src, pair_src.reshape(-1)])
    all_dst = jnp.concatenate([k_dst, pair_dst.reshape(-1)])
    all_w = jnp.concatenate([k_w, pair_w.reshape(-1)])
    all_via = jnp.concatenate([k_via, pair_via.reshape(-1)])

    o_src, o_dst, o_w, o_via, n_unique = gcsr.dedup_min_edges(
        all_src, all_dst, all_w, all_via, n, e_cap)

    n_is = jnp.sum(in_is.astype(jnp.int32))
    return (o_src, o_dst, o_w, o_via, in_is, nbrs,
            n_unique, n_is, n_is_edges, rounds)


@partial(jax.jit, static_argnames=("n", "d_cap", "aug_cap", "directed"),
         donate_argnames=("src", "dst", "w", "via", "active", "level_dev",
                          "up_ids", "up_w", "up_via", "up_in"))
def _peel_step(src, dst, w, via, active, level_dev, up_ids, up_w, up_via,
               rng, n_verts, lvl, n: int, d_cap: int, aug_cap: int,
               directed: bool = False, up_in=()):
    """One device-resident hierarchy level.

    Runs ``peel_level`` and folds the host-side bookkeeping of the
    original loop into the same jitted call: level recording and up-edge
    recording under the IS mask, active-set update, and the running
    ``|V|+|E|/2`` size for the stop rule (``|V|+|E|`` over arcs when
    ``directed``). ``lvl`` and ``n_verts`` are traced scalars so the call
    compiles once per (n, d_cap, aug_cap). ``directed`` records the
    out-adjacency in ``up_*`` and the in-adjacency in ``up_in``, an
    ``(ids, w, via)`` triple returned last (empty when undirected).

    Returns the updated state plus ``stats`` int32[6] =
    ``[n_is, n_unique, n_is_edges, mis_rounds, new_size, bound]`` — the
    one small per-level transfer the host reads; ``bound`` is the next
    level's eligible-degree bound, from which the host sizes its
    augmentation buffer (``level_aug_cap``). When the IS is empty the
    state update is the identity (the host then stops at level ``lvl``
    with the pre-step graph as the core, exactly like the host loop
    that breaks before recording).
    """
    rng, sub = jax.random.split(rng)
    (o_src, o_dst, o_w, o_via, in_is, nbrs,
     n_unique, n_is, n_is_edges, rounds) = peel_level(
        src, dst, w, via, active, sub, n, d_cap, aug_cap, directed)

    has_is = n_is > 0
    # record level + up-edges under the IS mask (row n of up_* is the
    # sentinel row — the mask is False there by construction)
    rec = jnp.concatenate([in_is, jnp.zeros((1,), bool)])
    level_dev = jnp.where(in_is, lvl.astype(jnp.int32), level_dev)
    up_ids = jnp.where(rec[:, None], nbrs[0][0], up_ids)
    up_w = jnp.where(rec[:, None], nbrs[0][1], up_w)
    up_via = jnp.where(rec[:, None], nbrs[0][2], up_via)
    if directed:
        up_in = tuple(jnp.where(rec[:, None], new, old)
                      for new, old in zip(nbrs[1], up_in))
    active = active & ~in_is
    # keep the pre-step edge list when the IS is empty: that graph IS the
    # core (dedup of an already-deduped list is value-identical, but the
    # guard makes the no-op explicit)
    src = jnp.where(has_is, o_src, src)
    dst = jnp.where(has_is, o_dst, dst)
    w = jnp.where(has_is, o_w, w)
    via = jnp.where(has_is, o_via, via)

    n_verts = n_verts - n_is
    new_size = n_verts + (n_unique if directed else n_unique // 2)
    bound = _eligible_degree_bound(src, dst, active, n, d_cap, directed)
    stats = jnp.stack([n_is, n_unique, n_is_edges, rounds, new_size, bound])
    return (src, dst, w, via, active, level_dev, up_ids, up_w, up_via,
            rng, n_verts, stats, up_in)


def build_hierarchy_device(n: int, src, dst, w, cfg: IndexConfig,
                           directed: bool = False) -> Hierarchy:
    """Device-resident level loop: one blocking host sync per level.

    All state (edge list, active set, level assignment, up-edge matrix)
    stays on device across levels in donated buffers; the host reads one
    int32[6] stat vector per level to apply the §5.1 stop rule and the
    capacity checks and to size the next level's augmentation buffer,
    then pulls everything once after the loop.
    ``directed`` takes ``src -> dst`` as arcs and also records the
    in-adjacency up-edges (``Hierarchy.up_in``), in the same pull.

    Spans (``repro.obs.span``): ``islabel.build.peel.upload`` around the
    edge upload, one ``islabel.build.peel.level`` per ``_peel_step``
    (attribute ``level``) and ``islabel.build.peel.pull`` around the
    final read.
    """
    m0 = len(src)
    e_cap = cfg.e_cap(m0)
    aug_ceiling = cfg.aug_cap(m0)
    bound = eligible_degree_bound_host(n, src, dst, cfg.d_cap, directed)
    with span("islabel.build.peel.upload"):
        g = gcsr.from_host_edges(src, dst, w, n, e_cap)

    def up_family():
        return (jnp.full((n + 1, cfg.d_cap), n, jnp.int32),    # ids
                jnp.full((n + 1, cfg.d_cap), jnp.inf, jnp.float32),
                jnp.full((n + 1, cfg.d_cap), -1, jnp.int32))

    state = (g.src, g.dst, g.weight, g.via,
             jnp.ones(n, bool),                              # active
             jnp.zeros(n, jnp.int32),                        # level
             *up_family(),                                   # up_*
             jax.random.PRNGKey(cfg.seed),
             jnp.int32(n))                                   # n_verts
    up_in = up_family() if directed else ()

    graph_sizes = [n + (m0 if directed else m0 // 2)]
    level_sizes, mis_rounds, edges, is_edges, aug_caps = [], [], [], [], []
    k = 1
    peel_iters = 0
    with hsync.sync_span() as syncs:
        for i in range(1, cfg.k_max + 1):
            peel_iters = i
            aug_cap = level_aug_cap(bound, aug_ceiling)
            aug_caps.append(aug_cap)
            with span("islabel.build.peel.level", level=i):
                *state, stats, up_in = _peel_step(
                    *state, jnp.int32(i), n, cfg.d_cap, aug_cap, directed,
                    up_in)
                # the single blocking transfer of the level: stop-rule
                # scalar, overflow flags and the next bound in one read
                n_is, n_unique, n_is_edges, rounds, new_size, bound = (
                    int(x) for x in hsync.host_read(stats))
            edges.append(n_unique)
            is_edges.append(n_is_edges)
            if n_unique > e_cap:
                raise RuntimeError(
                    f"edge capacity overflow at level {i}: {n_unique} > "
                    f"{e_cap}; raise IndexConfig.e_cap_factor")
            if n_is_edges > aug_cap:
                raise RuntimeError(
                    f"augmentation buffer overflow at level {i}: "
                    f"{n_is_edges} > {aug_cap}; raise "
                    f"IndexConfig.aug_cap_factor")
            if n_is == 0:
                k = i
                break
            level_sizes.append(n_is)
            mis_rounds.append(rounds)
            k = i + 1
            graph_sizes.append(new_size)
            if cfg.k_force:
                if k >= cfg.k_force:
                    break
            elif new_size > cfg.sigma * graph_sizes[-2]:
                break
    loop_syncs = syncs.count

    # one final pull of the whole hierarchy state
    (cur_src, cur_dst, cur_w, cur_via, _active, level_dev,
     up_ids_d, up_w_d, up_via_d, _rng, _nv) = state
    with span("islabel.build.peel.pull"):
        (level, up_ids, up_w, up_via, c_src_p, c_dst_p, c_w_p, c_via_p,
         up_in) = hsync.host_read((level_dev, up_ids_d, up_w_d, up_via_d,
                                   cur_src, cur_dst, cur_w, cur_via, up_in))
    level = np.array(level)
    level[level == 0] = k
    mask = c_src_p < n
    return Hierarchy(n=n, k=k, level=level, up_ids=np.array(up_ids),
                     up_w=np.array(up_w), up_via=np.array(up_via),
                     up_in=tuple(np.array(a) for a in up_in) or None,
                     core_src=c_src_p[mask], core_dst=c_dst_p[mask],
                     core_w=c_w_p[mask], core_via=c_via_p[mask],
                     level_sizes=level_sizes, graph_sizes=graph_sizes,
                     mis_rounds=mis_rounds, host_syncs=loop_syncs,
                     peel_iters=peel_iters, e_cap=e_cap, aug_caps=aug_caps,
                     edges=edges, is_edges=is_edges)


def build_hierarchy_host(n: int, src, dst, w, cfg: IndexConfig) -> Hierarchy:
    """Original host-driven loop (reference for the bitwise build gate):
    per-level scalar syncs + full neighbor-matrix round trips to numpy."""
    m0 = len(src)
    e_cap = cfg.e_cap(m0)
    aug_cap = cfg.aug_cap(m0)
    g = gcsr.from_host_edges(src, dst, w, n, e_cap)
    rng = jax.random.PRNGKey(cfg.seed)

    level = np.zeros(n, np.int32)
    up_ids = np.full((n + 1, cfg.d_cap), n, np.int32)
    up_w = np.full((n + 1, cfg.d_cap), np.inf, np.float32)
    up_via = np.full((n + 1, cfg.d_cap), -1, np.int32)
    active = jnp.ones(n, bool)

    cur_src, cur_dst, cur_w, cur_via = g.src, g.dst, g.weight, g.via
    n_verts = n
    n_edges = m0
    graph_sizes = [n_verts + n_edges // 2]
    level_sizes, mis_rounds, edges, is_edges = [], [], [], []
    k = 1
    peel_iters = 0
    with hsync.sync_span() as syncs:
        for i in range(1, cfg.k_max + 1):
            peel_iters = i
            rng, sub = jax.random.split(rng)
            (o_src, o_dst, o_w, o_via, in_is, ((nbr_ids, nbr_w, nbr_via),),
             n_unique, n_is, n_is_edges, rounds) = peel_level(
                cur_src, cur_dst, cur_w, cur_via, active, sub, n, cfg.d_cap,
                aug_cap)
            n_is_h = int(hsync.host_read(n_is))
            n_unique_h = int(hsync.host_read(n_unique))
            edges.append(n_unique_h)
            if n_unique_h > e_cap:
                raise RuntimeError(
                    f"edge capacity overflow at level {i}: "
                    f"{n_unique_h} > {e_cap}; "
                    f"raise IndexConfig.e_cap_factor")
            is_edges.append(int(hsync.host_read(n_is_edges)))
            if is_edges[-1] > aug_cap:
                raise RuntimeError(
                    f"augmentation buffer overflow at level {i}: "
                    f"{is_edges[-1]} > {aug_cap}; raise "
                    f"IndexConfig.aug_cap_factor")
            if n_is_h == 0:
                k = i
                break
            # record level + up-edges on host
            is_mask = hsync.host_read(in_is)
            level[is_mask] = i
            up_ids[:n][is_mask] = hsync.host_read(nbr_ids)[:n][is_mask]
            up_w[:n][is_mask] = hsync.host_read(nbr_w)[:n][is_mask]
            up_via[:n][is_mask] = hsync.host_read(nbr_via)[:n][is_mask]
            active = active & ~in_is
            level_sizes.append(n_is_h)
            mis_rounds.append(int(hsync.host_read(rounds)))

            n_verts -= n_is_h
            n_edges = n_unique_h
            new_size = n_verts + n_edges // 2
            cur_src, cur_dst, cur_w, cur_via = o_src, o_dst, o_w, o_via
            k = i + 1
            graph_sizes.append(new_size)
            if cfg.k_force:
                if k >= cfg.k_force:
                    break
            elif new_size > cfg.sigma * graph_sizes[-2]:
                break
    loop_syncs = syncs.count

    level[level == 0] = k

    c_src, c_dst, c_w, c_via = gcsr.to_host_coo(
        gcsr.EdgeList(cur_src, cur_dst, cur_w, cur_via, n_nodes=n))
    return Hierarchy(n=n, k=k, level=level, up_ids=up_ids, up_w=up_w,
                     up_via=up_via, core_src=c_src, core_dst=c_dst,
                     core_w=c_w, core_via=c_via, level_sizes=level_sizes,
                     graph_sizes=graph_sizes, mis_rounds=mis_rounds,
                     host_syncs=loop_syncs, peel_iters=peel_iters,
                     e_cap=e_cap, aug_caps=[aug_cap] * peel_iters,
                     edges=edges, is_edges=is_edges)


def build_hierarchy(n: int, src, dst, w, cfg: IndexConfig) -> Hierarchy:
    """Peel levels until the size-reduction stop rule (§5.1).

    Dispatches on ``cfg.builder``: ``device`` (default, sync-free level
    loop) or ``host`` (the original reference loop). Both are
    bitwise-identical at fixed seed — gated by ``bench_construction``.
    """
    if cfg.builder == "host":
        return build_hierarchy_host(n, src, dst, w, cfg)
    if cfg.builder != "device":
        raise ValueError(f"unknown IndexConfig.builder: {cfg.builder!r}")
    return build_hierarchy_device(n, src, dst, w, cfg)
