"""Versioned copy-on-write label blocks for live mutation under traffic.

The serving stack (docs/SERVING.md) compiles its entry points against a
fixed index: ``QueryEngine.batch_fn`` closes over the device label
arrays, so swapping in a mutated index means new closures and therefore
new XLA compiles on the read path — exactly what the
zero-compiles-after-warmup discipline forbids. This module inverts the
binding: the mutable state becomes a *traced argument*.

``VersionFamily`` fixes, once, every shape the query computation touches

  * ``core_cap``  — core-vertex slots (initial core + insert headroom),
  * ``edge_cap``  — COO core-edge slots (padded with ∞-weight sentinel
    edges between sentinel slots: min-plus no-ops),
  * ``ell_width``/``vp`` — the pinned ELL layout for the kernel path
    (``ell_layout`` widths are data-dependent, so the family asserts
    the post-mutation width still fits),

and jits ``run(state, s, t)`` entry points over a ``VersionState``
pytree. Every version of the index is a new pytree with identical
treedef/shapes/dtypes, so a hot swap is a pointer change — the compiled
executables survive untouched. Unused capacity is inert by min-plus
algebra: empty core slots hold +inf seeds (never the argmin), sentinel
edges add +inf (never relax anything).

§8.3 mutations are applied copy-on-write through the shared host
mutators in ``repro.core.index`` (``apply_insert_host`` /
``apply_delete_host``): ``LabelBlockStore`` keeps the [n+1, l_cap]
label planes as immutable row blocks; a mutation materializes writable
copies, and ``commit`` shares every block the touched rows missed.
Device propagation is an incremental row scatter, not a re-upload.

``VersionManager`` strings this together: ``apply(ops)`` produces a new
immutable ``IndexVersion`` (monotonic vid, cloned host oracle for
audits, fresh state pytree, committed store) and atomically republishes
``current``; readers pin versions with ``acquire``/``release`` so a
retired version is only dropped once its last in-flight batch drains.

Exactness domain (validated by tests/test_mutation_diff.py): in strict
mode the manager admits *core-attached* inserts (every neighbor at
level k — initial core vertices or live inserted ones) and deletes of
previously-inserted vertices. Within that domain every served distance
is bitwise equal to a from-scratch rebuild; see docs/MUTATION.md for
why arbitrary attachments are lazily-correct but not rebuild-identical.
"""
from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import (_core_relax_ell, _core_relax_fused,
                                 core_relax, fused_fits,
                                 label_intersect_rows_dispatch)
from repro.core.index import (ISLabelIndex, apply_delete_host,
                              apply_insert_host)
from repro.core.labels import (LabelCompressionError, LabelRows,
                               decode_rows, encode_labels)
from repro.kernels.backend import pallas_interpret, resolve_backend
from repro.kernels.spmv_relax.kernel import LANES
from repro.kernels.spmv_relax.ops import ell_layout

__all__ = [
    "MutationOp", "VersionState", "VersionFamily", "FamilyCapacityError",
    "LabelBlockStore", "IndexVersion", "VersionManager",
]


class FamilyCapacityError(RuntimeError):
    """A mutation outgrew the family's fixed shapes — the serving
    process must rebuild a wider family (recompiles) to admit it."""


class MutationOp(NamedTuple):
    """One §8.3 mutation. kind ∈ {"insert", "delete"}; nbrs/ws describe
    the inserted vertex's edges (ignored for deletes)."""
    kind: str
    u: int
    nbrs: tuple = ()
    ws: tuple = ()


class VersionState(NamedTuple):
    """The traced-argument pytree a jitted family entry point consumes.

    All leaves are device arrays with family-fixed shapes:
      lbl_ids/lbl_d   [n+1, l_cap]      label planes — in a compressed
                      family these hold the *encoded* planes (int16
                      deltas / int32 distances, core/labels.py)
      lbl_base        [n+1]             delta16 row bases; None in an
                      uncompressed family (a None leaf is an empty
                      pytree subtree, so the treedef stays fixed per
                      family and COW swaps never recompile)
      core_slot       [n+1]             vertex -> core slot (core_cap = none)
      ce_src/ce_dst   [edge_cap]        COO slot edges, sentinel-padded
      ce_w            [edge_cap]        weights, +inf padding
      nbr_ids/nbr_w   [vp, ell_width]   pinned ELL planes (kernel path)
    """
    lbl_ids: jnp.ndarray
    lbl_d: jnp.ndarray
    core_slot: jnp.ndarray
    ce_src: jnp.ndarray
    ce_dst: jnp.ndarray
    ce_w: jnp.ndarray
    nbr_ids: jnp.ndarray
    nbr_w: jnp.ndarray
    lbl_base: jnp.ndarray | None = None


class VersionFamily:
    """Fixed-shape compiled query family shared by all versions.

    ``mu_fn``/``full_fn`` mirror ``QueryEngine.mu_batch_fn``/``batch_fn``
    (same kernels, same two stages of Algorithm 1) but take the
    ``VersionState`` as an argument instead of closing over it. One
    compile per (entry point, backend, batch shape) for the lifetime of
    the family, regardless of how many versions flow through.
    """

    def __init__(self, n: int, core_cap: int, edge_cap: int,
                 ell_width: int, *, bq: int = 8,
                 codec: str = "none", d_dtype: str | None = None):
        if core_cap < 1:
            raise ValueError("core_cap must be >= 1")
        self.n = n
        self.core_cap = core_cap
        self.edge_cap = edge_cap
        self.ell_width = ell_width
        self.bq = bq
        self.vp = -(-(core_cap + 1) // LANES) * LANES
        self.max_rounds = core_cap          # while_loop exits at fixpoint
        # label codec pin: every version of the family must encode the
        # same way or the state dtypes (and the compiled fns) would move
        self.codec = codec
        self.d_dtype = d_dtype
        # fused single-launch relaxation when the family's pinned core
        # and ELL width admit it, else the XLA gather round
        self.relax_mode = ("fused" if fused_fits(self.vp, ell_width, bq)
                           else "ell_xla")
        self._mu_fns: dict = {}
        self._full_fns: dict = {}

    def _rows(self, state: VersionState, idx) -> LabelRows:
        if self.codec == "none":
            return LabelRows(state.lbl_ids[idx], None, state.lbl_d[idx])
        return LabelRows(state.lbl_ids[idx], state.lbl_base[idx],
                         state.lbl_d[idx])

    # ------------------------------------------------------- entry points
    def mu_fn(self, backend: str | None = None):
        """Jitted ``run(state, s, t) -> mu float32[Q]`` (Equation 1)."""
        backend = resolve_backend(backend)
        if backend not in self._mu_fns:
            n, codec = self.n, self.codec

            def run(state, s, t):
                return label_intersect_rows_dispatch(
                    self._rows(state, s), self._rows(state, t), n, codec,
                    backend)

            self._mu_fns[backend] = jax.jit(run)
        return self._mu_fns[backend]

    def full_fn(self, backend: str | None = None):
        """Jitted ``run(state, s, t) -> (ans float32[Q], rounds int32)``
        — both stages of Algorithm 1 over the family shapes."""
        backend = resolve_backend(backend)
        if backend not in self._full_fns:
            n, cap, codec = self.n, self.core_cap, self.codec
            max_rounds, bq = self.max_rounds, self.bq
            interp = False if backend == "reference" \
                else pallas_interpret(backend)

            def seed(state, ids, d):
                q = ids.shape[0]
                slot = state.core_slot[jnp.minimum(ids, n)]
                out = jnp.full((q, cap + 1), jnp.inf, jnp.float32)
                ridx = jnp.broadcast_to(jnp.arange(q)[:, None], slot.shape)
                return out.at[ridx, slot].min(
                    jnp.where(ids < n, d, jnp.inf))

            def run(state, s, t):
                rows_s = self._rows(state, s)
                rows_t = self._rows(state, t)
                mu = label_intersect_rows_dispatch(rows_s, rows_t, n,
                                                   codec, backend)
                ids_s, d_s = decode_rows(rows_s, n, codec)
                ids_t, d_t = decode_rows(rows_t, n, codec)
                seed_s = seed(state, ids_s, d_s)
                seed_t = seed(state, ids_t, d_t)
                if backend == "reference":
                    ans, _, _, rounds = core_relax(
                        seed_s, seed_t, state.ce_src, state.ce_dst,
                        state.ce_w, mu, cap, max_rounds)
                elif self.relax_mode == "fused":
                    ans, _, _, rounds = _core_relax_fused(
                        seed_s, seed_t, state.nbr_ids, state.nbr_w, mu,
                        cap, max_rounds, interp, bq)
                else:
                    ans, _, _, rounds = _core_relax_ell(
                        seed_s, seed_t, state.nbr_ids, state.nbr_w, mu,
                        cap, max_rounds)
                return ans, rounds

            self._full_fns[backend] = jax.jit(run)
        return self._full_fns[backend]

    def cache_sizes(self, backend: str | None = None) -> dict:
        """Compiled-shape counts per entry point (the zero-recompile
        probe: serving must never grow these after warmup)."""
        backend = resolve_backend(backend)
        out = {}
        for name, fns in (("mu", self._mu_fns), ("full", self._full_fns)):
            fn = fns.get(backend)
            out[name] = int(fn._cache_size()) if fn is not None else 0
        return out

    # ---------------------------------------------------------- state build
    def build_ell(self, src_slots, dst_slots, w):
        """Scatter real slot-edges into the family's pinned ELL planes.

        ``ell_layout`` picks a data-dependent width; the family asserts
        it still fits ``ell_width`` so kernel-path shapes never move.
        """
        dst_slots = np.asarray(dst_slots, np.int64)
        order, rows, slots, width = ell_layout(self.core_cap + 1, dst_slots)
        if width > self.ell_width:
            raise FamilyCapacityError(
                f"core in-degree needs ELL width {width} > family "
                f"{self.ell_width}; rebuild with more ell_headroom")
        ids = np.zeros((self.vp, self.ell_width), np.int32)
        ws = np.full((self.vp, self.ell_width), np.inf, np.float32)
        if len(dst_slots):
            ids[rows, slots] = np.asarray(src_slots, np.int32)[order]
            ws[rows, slots] = np.asarray(w, np.float32)[order]
        return jnp.asarray(ids), jnp.asarray(ws)

    def pad_coo(self, src_slots, dst_slots, w):
        """COO slot-edges padded to ``edge_cap`` with sentinel->sentinel
        +inf edges (scatter-min no-ops on the parked column)."""
        m = len(src_slots)
        if m > self.edge_cap:
            raise FamilyCapacityError(
                f"{m} core edges exceed family edge_cap {self.edge_cap}; "
                f"rebuild with more edge_headroom")
        ce_src = np.full(self.edge_cap, self.core_cap, np.int32)
        ce_dst = np.full(self.edge_cap, self.core_cap, np.int32)
        ce_w = np.full(self.edge_cap, np.inf, np.float32)
        ce_src[:m] = np.asarray(src_slots, np.int32)
        ce_dst[:m] = np.asarray(dst_slots, np.int32)
        ce_w[:m] = np.asarray(w, np.float32)
        return ce_src, ce_dst, ce_w


class LabelBlockStore:
    """Immutable blocked view of the [n+1, l_cap] label planes.

    ``writable()`` materializes full writable copies for the host
    mutators; ``commit(rows)`` builds the successor store, re-slicing
    only the blocks containing touched rows and *sharing* every other
    block object with this store (copy-on-write at block granularity).
    """

    def __init__(self, blocks: list, n_rows: int, block_rows: int):
        self._blocks = blocks        # [(ids, d, pred)] read-only np arrays
        self.n_rows = n_rows
        self.block_rows = block_rows

    @staticmethod
    def from_arrays(ids, d, pred, block_rows: int = 256) -> "LabelBlockStore":
        ids = np.asarray(ids)
        d = np.asarray(d)
        pred = np.asarray(pred)
        n_rows = ids.shape[0]
        blocks = []
        for lo in range(0, n_rows, block_rows):
            hi = min(lo + block_rows, n_rows)
            blk = (ids[lo:hi].copy(), d[lo:hi].copy(), pred[lo:hi].copy())
            for a in blk:
                a.setflags(write=False)
            blocks.append(blk)
        return LabelBlockStore(blocks, n_rows, block_rows)

    @property
    def num_blocks(self) -> int:
        return len(self._blocks)

    def arrays(self):
        """Read-only concatenated (ids, d, pred) planes."""
        ids = np.concatenate([b[0] for b in self._blocks])
        d = np.concatenate([b[1] for b in self._blocks])
        pred = np.concatenate([b[2] for b in self._blocks])
        return ids, d, pred

    def writable(self):
        """Fresh writable full copies for the host mutators."""
        ids, d, pred = self.arrays()
        return ids.copy(), d.copy(), pred.copy()

    def commit(self, ids_h, d_h, pred_h, rows) -> "LabelBlockStore":
        """Successor store: dirty blocks re-sliced from the mutated host
        arrays, clean blocks shared by reference."""
        dirty = {int(r) // self.block_rows for r in np.asarray(rows).ravel()}
        blocks = []
        for i, blk in enumerate(self._blocks):
            if i in dirty:
                lo = i * self.block_rows
                hi = min(lo + self.block_rows, self.n_rows)
                nb = (ids_h[lo:hi].copy(), d_h[lo:hi].copy(),
                      pred_h[lo:hi].copy())
                for a in nb:
                    a.setflags(write=False)
                blocks.append(nb)
            else:
                blocks.append(blk)
        return LabelBlockStore(blocks, self.n_rows, self.block_rows)

    def shared_blocks(self, other: "LabelBlockStore") -> int:
        """How many block objects two stores share (COW accounting)."""
        mine = {id(b[0]) for b in self._blocks}
        return sum(1 for b in other._blocks if id(b[0]) in mine)


@dataclasses.dataclass
class IndexVersion:
    """One immutable snapshot: the state pytree the compiled family
    consumes, the COW store it came from, and a cloned ``ISLabelIndex``
    whose host oracle answers audit queries for exactly this version."""
    vid: int
    index: ISLabelIndex
    state: VersionState
    store: LabelBlockStore
    mu_mask: np.ndarray          # bool[n]: Type-1-safe endpoints
    touched_rows: np.ndarray     # rows rewritten vs the parent version
    swap_seconds: float = 0.0
    # per-stage wall time of the apply that produced this version
    # (cow_apply / device_update / publish) — the mutation-lane trace
    # spans (docs/OBSERVABILITY.md) are cut from these
    stage_seconds: dict = dataclasses.field(default_factory=dict)

    @property
    def n_core(self) -> int:
        return len(self.index.core_ids)


def _clone_index(index: ISLabelIndex) -> ISLabelIndex:
    """Snapshot clone sharing immutable arrays. ``level`` is the one
    array the host mutators write in place, so it is copied; the core
    COO arrays are rebound (concatenate/filter), never mutated. The
    replace() resets the lazy caches (init=False fields)."""
    clone = dataclasses.replace(index)
    clone.level = index.level.copy()
    return clone


class VersionManager:
    """Monotonic version chain with refcounted drain-before-release.

    Single-writer: ``apply`` runs on the serving thread between
    micro-batches. ``current`` republishes atomically (one reference
    assignment); readers ``acquire()`` the version they execute against
    and ``release()`` it after the batch completes, so ``retire``-ing an
    old version only drops it once no in-flight batch pins it.
    """

    def __init__(self, family: VersionFamily, v0: IndexVersion, *,
                 strict: bool = True):
        self.family = family
        self.strict = strict
        self.current = v0
        self._versions = {v0.vid: v0}
        self._refs = {v0.vid: 0}
        self._retired: set = set()
        self._next_vid = v0.vid + 1
        self._core_slot = None       # int32[n+1], set by from_index
        self._next_slot = 0
        self._inserted_live: set = set()

    # ------------------------------------------------------------- build
    @staticmethod
    def from_index(index: ISLabelIndex, *, core_headroom: int = 64,
                   edge_headroom: int = 512, ell_headroom: int = 32,
                   block_rows: int = 256,
                   strict: bool = True) -> "VersionManager":
        from repro.serve.engine import mu_exact_mask
        n_core0 = len(index.core_ids)
        if n_core0 == 0:
            raise ValueError("versioned serving needs a non-empty core: "
                             "strict-mode inserts attach to core vertices")
        core_cap = n_core0 + core_headroom
        edge_cap = len(index.core_src) + edge_headroom
        slot = np.full(index.n + 1, core_cap, np.int32)
        slot[index.core_ids] = np.arange(n_core0, dtype=np.int32)
        _, _, _, base_w = ell_layout(core_cap + 1, slot[index.core_dst])
        ell_width = -(-(base_w + ell_headroom) // 16) * 16
        # the family pins the index's label codec: compressed versions
        # flow through COW swaps with the same state dtypes/treedef
        eng = index.engine
        codec = eng.codec
        d_dtype = None
        if codec != "none":
            d_dtype = ("int32" if eng.enc_d.dtype == jnp.int32
                       else "float32")
        family = VersionFamily(index.n, core_cap, edge_cap, ell_width,
                               codec=codec, d_dtype=d_dtype)
        store = LabelBlockStore.from_arrays(
            np.asarray(index.lbl_ids), np.asarray(index.lbl_d),
            np.asarray(index.lbl_pred), block_rows=block_rows)
        mgr = VersionManager(family, IndexVersion(
            vid=0, index=index, state=None, store=store,
            mu_mask=mu_exact_mask(index),
            touched_rows=np.zeros(0, np.int64)), strict=strict)
        mgr._core_slot = slot
        mgr._next_slot = n_core0
        mgr.current.state = mgr._build_state(
            eng.enc_ids, eng.enc_d, index, slot, lbl_base=eng.enc_base)
        return mgr

    def _build_state(self, lbl_ids_dev, lbl_d_dev, index, slot,
                     lbl_base=None) -> VersionState:
        src_slots = slot[index.core_src]
        dst_slots = slot[index.core_dst]
        ce_src, ce_dst, ce_w = self.family.pad_coo(src_slots, dst_slots,
                                                   index.core_w)
        nbr_ids, nbr_w = self.family.build_ell(src_slots, dst_slots,
                                               index.core_w)
        return VersionState(
            lbl_ids=lbl_ids_dev, lbl_d=lbl_d_dev, lbl_base=lbl_base,
            core_slot=jnp.asarray(slot),
            ce_src=jnp.asarray(ce_src), ce_dst=jnp.asarray(ce_dst),
            ce_w=jnp.asarray(ce_w), nbr_ids=nbr_ids, nbr_w=nbr_w)

    # ------------------------------------------------------------- apply
    def apply(self, ops) -> IndexVersion:
        """Copy-on-write §8.3 batch -> new published version.

        On any failure (capacity, strict-domain violation) the manager
        and the current version are untouched — mutations land in local
        copies and commit only on success.
        """
        from repro.serve.engine import mu_exact_mask
        t0 = time.perf_counter()
        cur = self.current
        fam = self.family
        clone = _clone_index(cur.index)
        ids_h, d_h, pred_h = cur.store.writable()
        slot = self._core_slot.copy()
        next_slot = self._next_slot
        live = set(self._inserted_live)
        touched: set = set()
        for op in ops:
            u = int(op.u)
            if op.kind == "insert":
                if self.strict:
                    bad = [int(v) for v in op.nbrs
                           if clone.level[int(v)] != clone.k]
                    if bad:
                        raise ValueError(
                            f"strict mode: insert({u}) attaches to "
                            f"non-core vertices {bad}; only core-attached "
                            f"inserts are rebuild-exact (docs/MUTATION.md)")
                apply_insert_host(clone, ids_h, d_h, pred_h, u,
                                  [int(v) for v in op.nbrs],
                                  [float(x) for x in op.ws], touched)
                if slot[u] == fam.core_cap:
                    if next_slot >= fam.core_cap:
                        raise FamilyCapacityError(
                            "core slots exhausted; rebuild with more "
                            "core_headroom")
                    slot[u] = next_slot
                    next_slot += 1
                live.add(u)
            elif op.kind == "delete":
                if self.strict and u not in live:
                    raise ValueError(
                        f"strict mode: delete({u}) targets a build-time "
                        f"vertex; only previously-inserted vertices delete "
                        f"rebuild-exactly (docs/MUTATION.md)")
                apply_delete_host(clone, ids_h, d_h, pred_h, u, touched)
                live.discard(u)
            else:
                raise ValueError(f"unknown mutation kind {op.kind!r}")
        t_host = time.perf_counter()
        rows = np.asarray(sorted(touched), np.int64)
        lbl_ids_dev, lbl_d_dev, lbl_pred_dev = self._scatter_rows(
            cur, ids_h, d_h, pred_h, rows)
        clone._install_labels(lbl_ids_dev, lbl_d_dev, lbl_pred_dev,
                              host=(ids_h, d_h, pred_h))
        if self.family.codec == "none":
            state = self._build_state(lbl_ids_dev, lbl_d_dev, clone, slot)
        else:
            enc_ids, enc_base, enc_d = self._scatter_state_rows(
                cur, ids_h, d_h, rows)
            state = self._build_state(enc_ids, enc_d, clone, slot,
                                      lbl_base=enc_base)
        version = IndexVersion(
            vid=self._next_vid, index=clone, state=state,
            store=cur.store.commit(ids_h, d_h, pred_h, rows),
            mu_mask=mu_exact_mask(clone), touched_rows=rows)
        t_dev = time.perf_counter()
        # success: commit manager state, then publish atomically
        self._core_slot, self._next_slot = slot, next_slot
        self._inserted_live = live
        self._next_vid += 1
        self._versions[version.vid] = version
        self._refs[version.vid] = 0
        self.current = version
        t_pub = time.perf_counter()
        version.swap_seconds = t_pub - t0
        version.stage_seconds = {"cow_apply": t_host - t0,
                                 "device_update": t_dev - t_host,
                                 "publish": t_pub - t_dev}
        return version

    def _scatter_rows(self, cur, ids_h, d_h, pred_h, rows):
        """Incremental device update: scatter only the touched rows into
        the parent version's device planes (allocating new arrays — the
        parent stays valid). Row counts are padded to the next power of
        two (repeating a row; identical payload, so duplicate scatter
        indices are deterministic) to bound the compile-shape count of
        this off-hot-path scatter."""
        if rows.size == 0:
            return cur.index.lbl_ids, cur.index.lbl_d, cur.index.lbl_pred
        pad = 1 << (int(rows.size) - 1).bit_length()
        r = np.concatenate([rows, np.full(pad - rows.size, rows[0],
                                          np.int64)])
        rj = jnp.asarray(r, jnp.int32)
        return (cur.index.lbl_ids.at[rj].set(jnp.asarray(ids_h[r])),
                cur.index.lbl_d.at[rj].set(jnp.asarray(d_h[r])),
                cur.index.lbl_pred.at[rj].set(jnp.asarray(pred_h[r])))

    def _scatter_state_rows(self, cur, ids_h, d_h, rows):
        """Compressed-family twin of ``_scatter_rows``: re-encode the
        touched rows (delta16 is row-local, so per-row re-encode under
        the family's pinned distance dtype is exact) and scatter them
        into the parent's encoded planes — same power-of-two row
        padding, same new-arrays-parent-stays-valid contract. A row
        that no longer fits the codec is a capacity failure, mirroring
        ELL-width overflow."""
        st = cur.state
        if rows.size == 0:
            return st.lbl_ids, st.lbl_base, st.lbl_d
        pad = 1 << (int(rows.size) - 1).bit_length()
        r = np.concatenate([rows, np.full(pad - rows.size, rows[0],
                                          np.int64)])
        try:
            delta, base, d_enc = encode_labels(
                ids_h[r], d_h[r], self.family.n,
                d_dtype=self.family.d_dtype)
        except LabelCompressionError as e:
            raise FamilyCapacityError(
                f"mutated label rows no longer fit the family's delta16 "
                f"codec ({e}); rebuild the family uncompressed") from e
        rj = jnp.asarray(r, jnp.int32)
        return (st.lbl_ids.at[rj].set(jnp.asarray(delta)),
                st.lbl_base.at[rj].set(jnp.asarray(base)),
                st.lbl_d.at[rj].set(jnp.asarray(d_enc)))

    # ---------------------------------------------------------- lifecycle
    def acquire(self) -> IndexVersion:
        """Pin and return the current version (refcount++)."""
        v = self.current
        self._refs[v.vid] += 1
        return v

    def release(self, version: IndexVersion):
        """Unpin; a retired version drops once its last reader leaves."""
        vid = version.vid
        if vid not in self._refs:
            return
        self._refs[vid] -= 1
        if self._refs[vid] <= 0 and vid in self._retired:
            self._drop(vid)

    def retire(self, version: IndexVersion):
        """Mark for release; dropped immediately if unpinned, otherwise
        when the last in-flight reader calls ``release``."""
        vid = version.vid
        if vid == self.current.vid:
            raise ValueError("cannot retire the current version")
        self._retired.add(vid)
        if self._refs.get(vid, 0) <= 0:
            self._drop(vid)

    def _drop(self, vid: int):
        self._versions.pop(vid, None)
        self._refs.pop(vid, None)
        self._retired.discard(vid)

    def drain(self) -> list:
        """Retire every non-current version; returns the vids still
        pinned by in-flight readers (empty = fully drained)."""
        for vid in list(self._versions):
            if vid != self.current.vid and vid not in self._retired:
                self.retire(self._versions[vid])
        return [vid for vid in self._versions if vid != self.current.vid]

    def live_versions(self) -> list:
        return sorted(self._versions)

    def refcount(self, version: IndexVersion) -> int:
        return self._refs.get(version.vid, 0)

    # ------------------------------------------------------------- warmup
    def warmup(self, batch_sizes, backend: str | None = None,
               mu_only: bool = False) -> dict:
        """Pre-compile the family entry points for every batch size
        (mirrors ``QueryEngine.warmup``); later versions reuse these
        executables — that is the point of the family."""
        state = self.current.state
        fns = [("mu", self.family.mu_fn(backend))]
        if not mu_only:
            fns.append(("full", self.family.full_fn(backend)))
        out = {}
        for name, fn in fns:
            for size in batch_sizes:
                z = jnp.zeros(int(size), jnp.int32)
                t0 = time.perf_counter()
                jax.block_until_ready(fn(state, z, z))
                out[(name, int(size))] = time.perf_counter() - t0
        return out
