"""Top-down vertex labeling (paper §6.1.4, Algorithm 4).

Corollary 1: label(v) = {(v,0)} ∪ merge of label(u) (+ edge weight) over
v's up-neighbors u in G_{ℓ(v)}. Processing levels k-1 → 1 guarantees
every up-neighbor's label is final before it is consumed.

The paper's block-nested-loop join becomes a vectorized *min-plus label
join*: gather up-neighbor label blocks, add the connecting edge weight,
then one stable per-row sort keyed on (ancestor id, distance) that
carries the predecessor along, + first-occurrence compact — the
fixed-shape analogue of the disk merge. The sort moves the payload with
its keys, so no per-element gather follows it; what the phase pays for
is the row width ``d_cap·l_cap + 1``. Rows are chunked so the
working set stays bounded (the chunk is the VMEM-resident tile of the
BNL join).

Sync model (docs/CONSTRUCTION.md): the chunk loop is sync-free. The
per-chunk l_cap overflow flag used to be read back (`bool(overflow)`)
after every chunk — one host stall per 4096 vertices; it now
accumulates into a per-level device vector inside the donated chunk
step, and the host checks it in one deferred read every
``cfg.sync_every`` levels (and once after the loop). On overflow the
build still raises with the offending level, exactly as the eager check
did; labels-in-progress are discarded with the raise, so no corrupted
state escapes.

Label rows are kept sorted by ancestor id — queries rely on this for the
merge-intersection.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sync as hsync
from repro.core.config import IndexConfig
from repro.core.hierarchy import Hierarchy
from repro.obs.profiler import span


@partial(jax.jit, static_argnames=("l_cap",),
         donate_argnames=("lbl_ids", "lbl_d", "lbl_pred", "ovf"))
def label_chunk_step(lbl_ids, lbl_d, lbl_pred, ovf, up_ids, up_w, verts,
                     lvl, l_cap: int):
    """Label one chunk of same-level vertices.

    lbl_*: [n+1, l_cap] global label arrays (row n = sentinel).
    ovf:   int32[k+1] per-level overflow accumulator (device-resident;
           slot ``lvl`` ORs in this chunk's l_cap overflow flag).
    up_*:  [n+1, d_cap] up-neighbor matrix.
    verts: int32[chunk] vertex ids of this level (padded with n).
    lvl:   int32 traced level index (for the overflow accumulator).
    """
    n = lbl_ids.shape[0] - 1
    c = verts.shape[0]
    u = up_ids[verts]                       # [c, d]
    w = up_w[verts]                         # [c, d]
    d_cap = u.shape[1]

    cand_ids = lbl_ids[u].reshape(c, d_cap * l_cap)
    cand_d = (w[:, :, None] + lbl_d[u]).reshape(c, d_cap * l_cap)
    cand_pred = jnp.broadcast_to(u[:, :, None],
                                 (c, d_cap, l_cap)).reshape(c, d_cap * l_cap)
    # the up-neighbor itself is an ancestor: it appears as (u, 0) in its own
    # label (self entry), so (u, w + 0) is generated automatically.
    self_ok = verts < n
    ids = jnp.concatenate([jnp.where(self_ok, verts, n)[:, None], cand_ids], 1)
    d = jnp.concatenate([jnp.where(self_ok, 0.0, jnp.inf)[:, None], cand_d], 1)
    pred = jnp.concatenate([jnp.full((c, 1), -1, jnp.int32), cand_pred], 1)
    d = jnp.where(ids >= n, jnp.inf, d)
    ids = jnp.where(jnp.isinf(d) & (pred >= 0), n, ids)  # drop dead candidates

    # one stable keyed sort per row by (id asc, d asc), pred carried along:
    # ties of (id, d) keep their input order
    ids, d, pred = jax.lax.sort((ids, d, pred), dimension=1,
                                is_stable=True, num_keys=2)

    is_first = jnp.concatenate(
        [jnp.ones((c, 1), bool), ids[:, 1:] != ids[:, :-1]], 1) & (ids < n)
    posn = jnp.cumsum(is_first.astype(jnp.int32), axis=1) - 1
    overflow = jnp.any(is_first & (posn >= l_cap))
    ovf = ovf.at[lvl].max(overflow.astype(jnp.int32))

    rows_ids = jnp.full((c, l_cap + 1), n, jnp.int32)
    rows_d = jnp.full((c, l_cap + 1), jnp.inf, jnp.float32)
    rows_pred = jnp.full((c, l_cap + 1), -1, jnp.int32)
    col = jnp.where(is_first, jnp.minimum(posn, l_cap), l_cap)
    ridx = jnp.broadcast_to(jnp.arange(c)[:, None], col.shape)
    rows_ids = rows_ids.at[ridx, col].set(jnp.where(is_first, ids, n),
                                          mode="drop")[:, :l_cap]
    rows_d = rows_d.at[ridx, col].set(jnp.where(is_first, d, jnp.inf),
                                      mode="drop")[:, :l_cap]
    rows_pred = rows_pred.at[ridx, col].set(jnp.where(is_first, pred, -1),
                                            mode="drop")[:, :l_cap]

    # write back (pad rows write the sentinel row with sentinel values — safe)
    lbl_ids = lbl_ids.at[verts].set(rows_ids)
    lbl_d = lbl_d.at[verts].set(rows_d)
    lbl_pred = lbl_pred.at[verts].set(rows_pred)
    return lbl_ids, lbl_d, lbl_pred, ovf


def _check_overflow(ovf, cfg: IndexConfig):
    """Deferred l_cap overflow check: one blocking read of the per-level
    accumulator. Reports the *highest* flagged level — levels are labeled
    k-1 → 1, so that is the first chunk that overflowed chronologically,
    matching the retired eager per-chunk check."""
    with span("islabel.build.label.check"):
        flags = hsync.host_read(ovf)
    hit = np.flatnonzero(flags)
    if len(hit):
        raise RuntimeError(
            f"label capacity overflow at level {int(hit.max())}: raise "
            f"IndexConfig.l_cap (currently {cfg.l_cap})")


def build_labels(hier: Hierarchy, cfg: IndexConfig):
    """Run Algorithm 4 over the hierarchy. Returns device label arrays
    ``(lbl_ids, lbl_d, lbl_pred)``; blocking syncs are limited to the
    deferred overflow checks (⌈k / sync_every⌉ + 1 total).

    Spans (``repro.obs.span``): one ``islabel.build.label.level`` per
    level (attributes ``level`` and ``chunks``) around its chunk
    dispatches, which return before the device has run them, and one
    ``islabel.build.label.check`` per deferred read."""
    n, k = hier.n, hier.k
    l_cap, chunk = cfg.l_cap, cfg.label_chunk
    sync_every = max(1, cfg.sync_every)

    lbl_ids = np.full((n + 1, l_cap), n, np.int32)
    lbl_d = np.full((n + 1, l_cap), np.inf, np.float32)
    core = np.flatnonzero(hier.level == k)
    lbl_ids[core, 0] = core
    lbl_d[core, 0] = 0.0

    lbl_ids = jnp.asarray(lbl_ids)
    lbl_d = jnp.asarray(lbl_d)
    lbl_pred = jnp.full((n + 1, l_cap), -1, jnp.int32)
    ovf = jnp.zeros(k + 1, jnp.int32)
    up_ids = jnp.asarray(hier.up_ids)
    up_w = jnp.asarray(hier.up_w)

    levels_done = 0
    for i in range(k - 1, 0, -1):
        verts = np.flatnonzero(hier.level == i)
        with span("islabel.build.label.level", level=i,
                  chunks=-(-len(verts) // chunk)):
            for lo in range(0, len(verts), chunk):
                part = verts[lo:lo + chunk]
                pad = np.full(chunk, n, np.int64)
                pad[:len(part)] = part
                lbl_ids, lbl_d, lbl_pred, ovf = label_chunk_step(
                    lbl_ids, lbl_d, lbl_pred, ovf, up_ids, up_w,
                    jnp.asarray(pad, jnp.int32), jnp.int32(i), l_cap)
        levels_done += 1
        if levels_done % sync_every == 0:
            _check_overflow(ovf, cfg)
    _check_overflow(ovf, cfg)
    return lbl_ids, lbl_d, lbl_pred
