"""Batched device-side shortest-path reconstruction (paper §8.1).

The host oracle (``ISLabelIndex.shortest_path``) walks the label pred
chain and the ``via`` bookkeeping with Python recursion — exact, but
one query at a time. This module is the fixed-shape, jitted analogue:
every stage operates on a whole ``[Q]`` batch at once and every array
has a static shape, so a single compiled executable serves any batch of
that shape (the serving contract mirrors ``QueryEngine``).

Stages (all inside one jitted function, see ``engine.PathEngine``):

  1. *meet* — Equation 1 (``label_intersect_mu``) gives μ and the
     meeting ancestor; the label-seeded core relaxation (the same
     ``CoreRelaxer`` dispatch the query hot path uses) gives the fixed
     point DS/DT, and ``argmin(DS + DT)`` the meeting core vertex.
     A query takes the *label route* when μ ≤ the core term, the *core
     route* otherwise (ties prefer the label route, like the oracle).

  2. *core parent chase* — predecessors are recovered from the fixed
     point itself: u is a parent of v iff ``DS[u] + w(u, v) == DS[v]``
     (exact float equality — at the Bellman-Ford fixed point the min is
     attained, so a parent always exists unless v is a label seed,
     ``DS[v] == seed[v]``, which ends the chase). Each chase step is a
     ``[Q, D]`` gather over the same ELL layout stage 2 consumes
     (with a via plane added), so no ``[Q, V, D]`` tensor is ever
     materialized and no extra state is carried through the relaxation.

  3. *stitch* — label hops of s, the reversed s-side core segment, the
     forward t-side core segment, and the reversed label hops of t are
     scattered into one ``[Q, hop_cap]`` edge list (vertex, via, w).

  4. *via expansion* — the recursive §8.1 expansion becomes an
     iterative insertion loop: every augmenting edge (a, b) with
     ``via = c`` splits into (a, c) + (c, b), whose vias/weights come
     from c's up-adjacency row. One round expands *every* pending edge
     in the batch via a prefix-sum scatter; nesting depth is bounded by
     the hierarchy height k, so the loop runs at most k rounds.

Fixed capacities: label chases are bounded by k (levels strictly
increase along the pred chain), core chases and the final path by
``hop_cap``. Overflow never aborts the batch — the query's ``ok`` flag
drops and the caller escalates to a larger ``hop_cap`` (the serving
layer shape-buckets on it; see docs/PATHS.md).

Weights are carried *per edge* through every split, so the returned
``[Q, hop_cap]`` weight plane holds original-graph edge weights whose
sum reproduces the served distance — the exactness gate asserted in
tests and ``benchmarks/bench_path.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def label_chase(lbl_ids, lbl_pred, up_ids, up_w, up_via, start, target,
                active, chase_cap: int, n: int):
    """Walk the label pred chain ``start -> target`` for a batch.

    Returns ``(hop_v, hop_via, hop_w, hops, ok)`` with ``hop_v[q, i]``
    the i-th path vertex (the edge i leads to vertex i+1; the final
    vertex ``target`` is implicit) and ``hops[q]`` the hop count.
    Queries with ``active=False`` report zero hops. ``ok`` drops when
    the chain is inconsistent or longer than ``chase_cap``.
    """
    q = start.shape[0]
    l_cap = lbl_ids.shape[1]
    hop_v = jnp.full((q, chase_cap), n, jnp.int32)
    hop_via = jnp.full((q, chase_cap), -1, jnp.int32)
    hop_w = jnp.zeros((q, chase_cap), jnp.float32)

    def cond(st):
        _, i, _, _, _, _, _, act = st
        return jnp.any(act) & (i < chase_cap)

    def body(st):
        cur, i, hv, hvia, hw, hops, ok, act = st
        row_ids = lbl_ids[cur]                          # [Q, L]
        j = jax.vmap(jnp.searchsorted)(row_ids, target)
        j = jnp.minimum(j, l_cap - 1)
        found = jnp.take_along_axis(row_ids, j[:, None], 1)[:, 0] == target
        u = jnp.take_along_axis(lbl_pred[cur], j[:, None], 1)[:, 0]
        urow = up_ids[cur]                              # [Q, d_cap]
        hit = urow == u[:, None]
        slot = jnp.argmax(hit, axis=1)
        step_ok = found & (u >= 0) & jnp.any(hit, axis=1)
        via = jnp.take_along_axis(up_via[cur], slot[:, None], 1)[:, 0]
        w = jnp.take_along_axis(up_w[cur], slot[:, None], 1)[:, 0]
        write = act & step_ok
        hv = hv.at[:, i].set(jnp.where(write, cur, hv[:, i]))
        hvia = hvia.at[:, i].set(jnp.where(write, via, hvia[:, i]))
        hw = hw.at[:, i].set(jnp.where(write, w, hw[:, i]))
        hops = hops + write.astype(jnp.int32)
        ok = ok & (~act | step_ok)
        cur = jnp.where(write, u, cur)
        act = write & (cur != target)
        return cur, i + 1, hv, hvia, hw, hops, ok, act

    act0 = active & (start != target)
    st = (start, jnp.int32(0), hop_v, hop_via, hop_w,
          jnp.zeros(q, jnp.int32), jnp.ones(q, bool), act0)
    cur, _, hop_v, hop_via, hop_w, hops, ok, act = jax.lax.while_loop(
        cond, body, st)
    ok = ok & ~act                  # ran out of chase_cap before target
    return hop_v, hop_via, hop_w, hops, ok


def core_chase(dvec, seed, ell_ids, ell_w, ell_via, core_gid, vstar, active,
               core_cap: int, n: int):
    """Parent-chase one direction's fixed point from ``vstar`` (local
    core index) back to a label seed.

    Step i records the parent edge walked: ``pv[q, i]`` the parent
    (global id), ``pvia``/``pw`` the via/weight of the edge between the
    previous chase vertex and that parent. Returns
    ``(pv, pvia, pw, steps, r_local, ok)`` — ``r_local`` is the seed
    core vertex the chase ended on (== ``vstar`` for zero steps).
    """
    q = dvec.shape[0]
    pv = jnp.full((q, core_cap), n, jnp.int32)
    pvia = jnp.full((q, core_cap), -1, jnp.int32)
    pw = jnp.zeros((q, core_cap), jnp.float32)

    def cond(st):
        _, i, _, _, _, _, _, act = st
        return jnp.any(act) & (i < core_cap)

    def body(st):
        cur, i, v, via_a, w_a, steps, ok, act = st
        dv = jnp.take_along_axis(dvec, cur[:, None], 1)[:, 0]
        sv = jnp.take_along_axis(seed, cur[:, None], 1)[:, 0]
        at_seed = dv == sv
        nbr = ell_ids[cur]                              # [Q, D]
        wr = ell_w[cur]
        vr = ell_via[cur]
        dnbr = jnp.take_along_axis(dvec, nbr, axis=1)
        cand = (dnbr + wr) == dv[:, None]
        hit = jnp.any(cand, axis=1)
        jsel = jnp.argmax(cand, axis=1)
        par = jnp.take_along_axis(nbr, jsel[:, None], 1)[:, 0]
        via = jnp.take_along_axis(vr, jsel[:, None], 1)[:, 0]
        w = jnp.take_along_axis(wr, jsel[:, None], 1)[:, 0]
        write = act & ~at_seed & hit
        v = v.at[:, i].set(jnp.where(write, core_gid[par], v[:, i]))
        via_a = via_a.at[:, i].set(jnp.where(write, via, via_a[:, i]))
        w_a = w_a.at[:, i].set(jnp.where(write, w, w_a[:, i]))
        steps = steps + write.astype(jnp.int32)
        ok = ok & (~act | at_seed | hit)
        cur = jnp.where(write, par, cur)
        act = write
        return cur, i + 1, v, via_a, w_a, steps, ok, act

    st = (vstar, jnp.int32(0), pv, pvia, pw, jnp.zeros(q, jnp.int32),
          jnp.ones(q, bool), active)
    cur, _, pv, pvia, pw, steps, ok, act = jax.lax.while_loop(cond, body, st)
    # a chase still active after core_cap steps never reached a seed
    dv = jnp.take_along_axis(dvec, cur[:, None], 1)[:, 0]
    sv = jnp.take_along_axis(seed, cur[:, None], 1)[:, 0]
    ok = ok & (~act | (dv == sv))
    return pv, pvia, pw, steps, cur, ok


def _scatter_rows(buf, vals, start, count, fill):
    """Write ``vals[q, :count[q]]`` at columns ``start[q] + i`` of the
    ``[Q, H+1]`` buffer (column H is the drop scratch)."""
    q, c = vals.shape
    h = buf.shape[1] - 1
    cols = jnp.arange(c)[None, :]
    valid = cols < count[:, None]
    tgt = jnp.minimum(jnp.where(valid, start[:, None] + cols, h), h)
    rows = jnp.broadcast_to(jnp.arange(q)[:, None], tgt.shape)
    return buf.at[rows, tgt].set(jnp.where(valid, vals, fill))


def _reverse_gather(arr, count, fill):
    """``out[q, j] = arr[q, count[q]-1-j]`` for j < count (fill after)."""
    q, c = arr.shape
    cols = jnp.arange(c)[None, :]
    idx = jnp.clip(count[:, None] - 1 - cols, 0, c - 1)
    out = jnp.take_along_axis(arr, idx, axis=1)
    return jnp.where(cols < count[:, None], out, fill)


def stitch(s, t, finite, hop_cap: int, n: int,
           ls_v, ls_via, ls_w, p_s,
           seg_s_v, seg_s_via, seg_s_w, m_s,
           vstar_g, seg_t_v, seg_t_via, seg_t_w, m_t,
           lt_v, lt_via, lt_w, p_t, x_t):
    """Assemble the four path pieces into one ``[Q, hop_cap]`` edge
    list. Pieces (forward order): label hops of s · reversed s-side
    core segment · forward t-side core segment · reversed label hops of
    t · the final vertex t. Returns ``(verts, evia, ew, length, ok)``
    with ``length`` the vertex count (0 for unreachable pairs)."""
    q = s.shape[0]
    h = hop_cap
    edges = p_s + m_s + m_t + p_t
    length = jnp.where(finite, edges + 1, 0)
    ok = length <= h

    verts = jnp.full((q, h + 1), n, jnp.int32)
    evia = jnp.full((q, h + 1), -1, jnp.int32)
    ew = jnp.zeros((q, h + 1), jnp.float32)

    zero = jnp.zeros(q, jnp.int32)
    p_s = jnp.where(finite, p_s, zero)
    m_s = jnp.where(finite, m_s, zero)
    m_t = jnp.where(finite, m_t, zero)
    p_t = jnp.where(finite, p_t, zero)

    # piece 1: label hops of s, forward
    verts = _scatter_rows(verts, ls_v, zero, p_s, n)
    evia = _scatter_rows(evia, ls_via, zero, p_s, -1)
    ew = _scatter_rows(ew, ls_w, zero, p_s, 0.0)
    # piece 2: s-side core segment, reversed (seed -> vstar)
    off = p_s
    verts = _scatter_rows(verts, _reverse_gather(seg_s_v, m_s, n),
                          off, m_s, n)
    evia = _scatter_rows(evia, _reverse_gather(seg_s_via, m_s, -1),
                         off, m_s, -1)
    ew = _scatter_rows(ew, _reverse_gather(seg_s_w, m_s, 0.0),
                       off, m_s, 0.0)
    # piece 3: t-side core segment, forward from vstar
    off = off + m_s
    v3 = jnp.concatenate([vstar_g[:, None], seg_t_v[:, :-1]], axis=1) \
        if seg_t_v.shape[1] > 0 else seg_t_v
    verts = _scatter_rows(verts, v3, off, m_t, n)
    evia = _scatter_rows(evia, seg_t_via, off, m_t, -1)
    ew = _scatter_rows(ew, seg_t_w, off, m_t, 0.0)
    # piece 4: label hops of t, reversed (x_t -> t); vertex j is
    # b_{p_t - j}: x_t at j = 0, then the chase vertices reversed
    off = off + m_t
    cols = jnp.arange(lt_v.shape[1])[None, :]
    idx = jnp.clip(p_t[:, None] - cols, 0, lt_v.shape[1] - 1)
    v4 = jnp.where(cols == 0, x_t[:, None],
                   jnp.take_along_axis(lt_v, idx, axis=1))
    verts = _scatter_rows(verts, v4, off, p_t, n)
    evia = _scatter_rows(evia, _reverse_gather(lt_via, p_t, -1),
                         off, p_t, -1)
    ew = _scatter_rows(ew, _reverse_gather(lt_w, p_t, 0.0), off, p_t, 0.0)
    # final vertex t
    tcol = jnp.minimum(jnp.where(finite, edges, h), h)
    verts = verts.at[jnp.arange(q), tcol].set(
        jnp.where(finite, t, verts[jnp.arange(q), tcol]))
    return verts[:, :h], evia[:, :h], ew[:, :h], length, ok


def expand_vias(verts, evia, ew, length, ok, up_ids, up_w, up_via,
                n: int, max_rounds: int):
    """Iteratively expand every augmenting edge in place (§8.1).

    Each round splits every edge (a, b) with ``via = c >= 0`` into
    (a, c) + (c, b) via a prefix-sum insertion scatter; sub-edge vias
    and weights come from c's up-adjacency row. Terminates in at most
    ``max_rounds`` (the hierarchy height bounds the nesting depth).
    """
    q, h = verts.shape
    rows = jnp.arange(q)

    def cond(st):
        _, evia_, _, _, _, it = st
        return jnp.any(evia_ >= 0) & (it < max_rounds)

    def body(st):
        v, evia_, ew_, length_, ok_, it = st
        edge_valid = jnp.arange(h)[None, :] < (length_[:, None] - 1)
        need = (evia_ >= 0) & edge_valid
        grow = need.astype(jnp.int32)
        shift = jnp.cumsum(grow, axis=1) - grow
        new_pos = jnp.arange(h)[None, :] + shift
        new_len = length_ + jnp.sum(grow, axis=1)
        ok_ = ok_ & (new_len <= h)

        b = jnp.concatenate([v[:, 1:], jnp.full((q, 1), n, jnp.int32)], 1)
        c = jnp.where(need, evia_, 0)
        crow = up_ids[c]                                # [Q, H, D]
        hit_a = crow == v[..., None]
        hit_b = crow == b[..., None]
        sa = jnp.argmax(hit_a, -1)[..., None]
        sb = jnp.argmax(hit_b, -1)[..., None]
        ok_ = ok_ & ~jnp.any(
            need & ~(jnp.any(hit_a, -1) & jnp.any(hit_b, -1)), axis=1)
        cvia = up_via[c]
        cw = up_w[c]
        via_ac = jnp.take_along_axis(cvia, sa, -1)[..., 0]
        w_ac = jnp.take_along_axis(cw, sa, -1)[..., 0]
        via_cb = jnp.take_along_axis(cvia, sb, -1)[..., 0]
        w_cb = jnp.take_along_axis(cw, sb, -1)[..., 0]

        vert_valid = jnp.arange(h)[None, :] < length_[:, None]
        tgt = jnp.minimum(jnp.where(vert_valid, new_pos, h), h)
        rr = jnp.broadcast_to(rows[:, None], tgt.shape)
        nv = jnp.full((q, h + 1), n, jnp.int32).at[rr, tgt].set(v)
        nvia = jnp.full((q, h + 1), -1, jnp.int32).at[rr, tgt].set(
            jnp.where(need, via_ac, evia_))
        nw = jnp.zeros((q, h + 1), jnp.float32).at[rr, tgt].set(
            jnp.where(need, w_ac, ew_))
        ins = jnp.minimum(jnp.where(need, new_pos + 1, h), h)
        nv = nv.at[rr, ins].set(jnp.where(need, c, nv[rr, ins]))
        nvia = nvia.at[rr, ins].set(jnp.where(need, via_cb, nvia[rr, ins]))
        nw = nw.at[rr, ins].set(jnp.where(need, w_cb, nw[rr, ins]))
        return (nv[:, :h], nvia[:, :h], nw[:, :h],
                jnp.minimum(new_len, h), ok_, it + 1)

    st = (verts, evia, ew, length, ok, jnp.int32(0))
    verts, evia, ew, length, ok, _ = jax.lax.while_loop(cond, body, st)
    # any via still pending means the round bound was hit (inconsistent
    # index) — never report such a path as valid
    ok = ok & ~jnp.any(evia >= 0, axis=1)
    return verts, ew, length, ok
