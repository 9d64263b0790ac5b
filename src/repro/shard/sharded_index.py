"""`ShardedIndex` — an IS-LABEL index hosted as P label partitions.

  sidx = ShardedIndex.from_index(idx, num_shards=4)      # slice + place
  sidx = ShardedIndex.build(n, src, dst, w, cfg, num_shards=4)
  ans, rounds = sidx.engine.batch_fn()(s, t)   # bitwise == unsharded
  sidx.save(dir); ShardedIndex.load(dir)
  DistanceServer(sidx)                         # serving, sharded lane

No device holds the full label table: shard p's block carries its
ancestor partition plus the replicated top hierarchy levels
(``partition.py``), stacked [P, n+1, cap_s] and laid over a 1-D
``jax.sharding.Mesh`` shard axis via the logical-axis rules
``GRAPH_INDEX_RULES`` below (label_shard → mesh shard; vertex rows,
levels, and the core graph replicated). Queries run
through ``ShardedQueryEngine`` (shard_map + one pmin per batch).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.config import BuildStats, IndexConfig
from repro.shard.partition import (LabelBlocks, assign_shards,
                                   partition_labels)
from repro.shard.query import ShardedQueryEngine

# Logical axis -> mesh axis. Label blocks are stacked [P, n+1, cap_s]
# with the leading label-partition axis laid over the mesh's "shard"
# axis; per-vertex rows ("vertex"), label slots, hierarchy levels, and
# the whole core graph stay replicated — the core search runs
# shard-locally (top levels are replicated into every label block) and
# only the Equation-1 partial minima cross shards (one collective per
# batch; docs/SHARDING.md).
GRAPH_INDEX_RULES = {
    "label_shard": "shard",   # one label partition per mesh slice
    "vertex": None,           # [n+1] rows: every shard sees all vertices
    "label_slot": None,       # padded per-shard label columns
    "level": None,            # hierarchy levels: replicated
    "core_vertex": None,      # core_pos / seed columns: replicated
    "core_edge": None,        # G_k COO arrays: replicated
}


def spec_for_axes(axes: tuple, rules: dict) -> P:
    return P(*(rules.get(ax) for ax in axes))


def tree_shardings(axes_tree, rules: dict, mesh):
    """Map a logical-axes tree to NamedShardings."""
    def one(ax):
        return NamedSharding(mesh, spec_for_axes(ax, rules))
    return jax.tree.map(one, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


# logical axes of every placed leaf, resolved through GRAPH_INDEX_RULES
_AXES_TREE = {
    "lbl_ids": ("label_shard", "vertex", "label_slot"),
    "lbl_d": ("label_shard", "vertex", "label_slot"),
    # compressed planes (core/labels.py delta16) shard like the planes
    # they encode; the per-row base drops the slot axis
    "lbl_delta": ("label_shard", "vertex", "label_slot"),
    "lbl_base": ("label_shard", "vertex"),
    "lbl_denc": ("label_shard", "vertex", "label_slot"),
    "core_pos": ("vertex",),
    "ce_src": ("core_edge",),
    "ce_dst": ("core_edge",),
    "ce_w": ("core_edge",),
}


def make_shard_mesh(num_shards: int) -> Mesh:
    """1-D mesh over the first ``num_shards`` local devices."""
    devs = jax.devices()
    if num_shards > len(devs):
        raise ValueError(
            f"num_shards={num_shards} exceeds the {len(devs)} available "
            f"device(s); simulate more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return Mesh(np.asarray(devs[:num_shards]), ("shard",))


@dataclasses.dataclass
class ShardedIndex:
    """Duck-types the `ISLabelIndex` surface the serving layer uses
    (n/k/level/stats/engine/query), with partitioned label state."""
    n: int
    k: int
    num_shards: int
    strategy: str
    replicate_top: int
    cfg: IndexConfig
    level: np.ndarray            # int32[n] (host, replicated concern)
    shard_of: np.ndarray         # int32[n+1], REPLICATED = -1
    entries_per_shard: np.ndarray  # int64[P]: owned+replicated per shard
    # per-shard label blocks [P, n+1, cap_s]; ids/d sharded over the
    # mesh, pred host-only (queries never read it — like the up-edge
    # matrix it exists for path reconstruction and save/load)
    lbl_ids: jnp.ndarray
    lbl_d: jnp.ndarray
    lbl_pred: np.ndarray
    # core graph (host, global ids) + host core position map
    core_ids: np.ndarray
    core_pos_host: np.ndarray
    core_src: np.ndarray
    core_dst: np.ndarray
    core_w: np.ndarray
    mesh: Mesh
    engine: ShardedQueryEngine
    stats: BuildStats
    # path-reconstruction state (host; queries never read it): the
    # core via bookkeeping and the up-adjacency matrices. None on
    # indexes saved before path support — path queries then raise.
    core_via: np.ndarray | None = None
    up_ids: np.ndarray | None = None
    up_w: np.ndarray | None = None
    up_via: np.ndarray | None = None
    _paths: object = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    # ---------------------------------------------------------- builders
    @staticmethod
    def build(n, src, dst, w, cfg: IndexConfig = IndexConfig(), *,
              num_shards: int = 1, strategy: str = "level",
              replicate_top: int = 1, mesh: Mesh | None = None
              ) -> "ShardedIndex":
        from repro.core.index import ISLabelIndex
        idx = ISLabelIndex.build(n, src, dst, w, cfg)
        return ShardedIndex.from_index(idx, num_shards, strategy=strategy,
                                       replicate_top=replicate_top, mesh=mesh)

    @staticmethod
    def from_index(index, num_shards: int, *, strategy: str = "level",
                   replicate_top: int = 1, mesh: Mesh | None = None
                   ) -> "ShardedIndex":
        """Partition an existing `ISLabelIndex` and place it on devices."""
        shard_of = assign_shards(index.level, index.k, num_shards,
                                 strategy=strategy,
                                 replicate_top=replicate_top)
        blocks = partition_labels(index.lbl_ids, index.lbl_d, index.lbl_pred,
                                  index.n, shard_of, num_shards)
        return ShardedIndex._assemble(
            n=index.n, k=index.k, cfg=index.cfg, level=index.level,
            shard_of=shard_of, blocks=blocks, core_ids=index.core_ids,
            core_pos=index.core_pos_host, core_src=index.core_src,
            core_dst=index.core_dst, core_w=index.core_w,
            stats=index.stats, strategy=strategy,
            replicate_top=replicate_top, mesh=mesh,
            core_via=index.core_via, up_ids=index.up_ids,
            up_w=index.up_w, up_via=index.up_via)

    @staticmethod
    def _assemble(*, n, k, cfg, level, shard_of, blocks: LabelBlocks,
                  core_ids, core_pos, core_src, core_dst, core_w, stats,
                  strategy, replicate_top, mesh, core_via=None,
                  up_ids=None, up_w=None, up_via=None) -> "ShardedIndex":
        num_shards = blocks.num_shards
        if mesh is None:
            mesh = make_shard_mesh(num_shards)
        axis = mesh.axis_names[0]
        if mesh.shape[axis] != num_shards:
            raise ValueError(f"mesh axis {axis!r} has size "
                             f"{mesh.shape[axis]}, need {num_shards}")
        shardings = tree_shardings(_AXES_TREE, GRAPH_INDEX_RULES, mesh)
        host = {
            "lbl_ids": blocks.ids, "lbl_d": blocks.d,
            "core_pos": core_pos,
            "ce_src": core_pos[core_src].astype(np.int32),
            "ce_dst": core_pos[core_dst].astype(np.int32),
            "ce_w": np.asarray(core_w, np.float32),
        }
        # per-shard blocks keep the [reals..., pads] row layout the
        # codec requires (partition_labels compacts in source order), so
        # compressed blocks encode row-locally per shard
        codec = "none"
        if cfg.label_dtype != "fp32":
            from repro.core.labels import encode_labels, try_encode_labels
            encode = (encode_labels if cfg.label_dtype == "compressed"
                      else try_encode_labels)
            enc = encode(blocks.ids, blocks.d, n)
            if enc is not None:
                codec = "delta16"
                host["lbl_delta"], host["lbl_base"], host["lbl_denc"] = enc
        dev = {name: jax.device_put(arr, shardings[name])
               for name, arr in host.items()}
        engine = ShardedQueryEngine(
            dev["lbl_ids"], dev["lbl_d"], dev["core_pos"],
            (dev["ce_src"], dev["ce_dst"], dev["ce_w"]),
            n=n, n_core=len(core_ids), mesh=mesh,
            max_rounds=cfg.max_relax_rounds, backend=cfg.query_backend,
            codec=codec,
            enc=None if codec == "none" else (dev["lbl_delta"],
                                              dev["lbl_base"],
                                              dev["lbl_denc"]))
        return ShardedIndex(
            n=n, k=k, num_shards=num_shards, strategy=strategy,
            replicate_top=replicate_top, cfg=cfg, level=np.asarray(level),
            shard_of=shard_of, entries_per_shard=np.asarray(blocks.entries),
            lbl_ids=dev["lbl_ids"], lbl_d=dev["lbl_d"],
            lbl_pred=np.asarray(blocks.pred), core_ids=np.asarray(core_ids),
            core_pos_host=np.asarray(core_pos),
            core_src=np.asarray(core_src), core_dst=np.asarray(core_dst),
            core_w=np.asarray(core_w), mesh=mesh, engine=engine, stats=stats,
            core_via=None if core_via is None else np.asarray(core_via),
            up_ids=None if up_ids is None else np.asarray(up_ids),
            up_w=None if up_w is None else np.asarray(up_w),
            up_via=None if up_via is None else np.asarray(up_via))

    # ------------------------------------------------------------- query
    def query(self, s, t, backend: str | None = None):
        """Exact batched distances — bitwise-equal to the unsharded
        ``ISLabelIndex.query`` on every backend."""
        return self.engine.query(s, t, backend)

    def query_host(self, s, t) -> np.ndarray:
        return np.asarray(self.query(np.atleast_1d(s), np.atleast_1d(t)))

    def query_types(self, s, t):
        return self.engine.classify(s, t, self.level, self.k)

    def shard_entry_counts(self) -> np.ndarray:
        """int64[P]: label entries held per shard (owned + replicated),
        recorded at partition time — no device round trip."""
        return self.entries_per_shard.copy()

    # ------------------------------------------------------------- paths
    def gather_label_rows(self):
        """Reassemble full ``[n+1, l_cap]`` label arrays by gathering
        every vertex's entries from the shard that owns their ancestor
        (plus the replicated top levels) — the bit-exact
        ``unpartition_labels`` inverse asserted in tests. Host-side."""
        from repro.shard.partition import unpartition_labels
        blocks = LabelBlocks(ids=np.asarray(self.lbl_ids),
                             d=np.asarray(self.lbl_d),
                             pred=np.asarray(self.lbl_pred),
                             entries=self.entries_per_shard)
        return unpartition_labels(blocks, self.n, self.cfg.l_cap)

    def path_engine(self):
        """Batched path reconstruction over the sharded index
        (docs/PATHS.md): label rows are gathered once from the owning
        shards' blocks and the identical ``repro.paths.PathEngine`` is
        built over them, so sharded and unsharded path answers agree
        bitwise. Paths are a lower-QPS workload than distances; the
        distance hot path keeps the labels partitioned."""
        if self._paths is None:
            if self.up_ids is None:
                raise ValueError(
                    "this ShardedIndex was saved without path state "
                    "(up-edge matrices); rebuild with "
                    "ShardedIndex.from_index to serve path queries")
            from repro.paths import PathEngine
            ids, d, pred = self.gather_label_rows()
            self._paths = PathEngine(
                n=self.n, k=self.k, lbl_ids=ids, lbl_d=d, lbl_pred=pred,
                up_ids=self.up_ids, up_w=self.up_w, up_via=self.up_via,
                core_ids=self.core_ids, core_pos=self.core_pos_host,
                core_src=self.core_src, core_dst=self.core_dst,
                core_w=self.core_w, core_via=self.core_via,
                max_rounds=self.cfg.max_relax_rounds,
                backend=self.cfg.query_backend,
                relaxer=self.engine.relaxer)
        return self._paths

    def shortest_paths(self, s, t, hop_cap: int = 256,
                       backend: str | None = None):
        """Batched shortest paths — same contract as
        ``ISLabelIndex.shortest_paths``."""
        return self.path_engine().paths(s, t, hop_cap=hop_cap,
                                        backend=backend)

    def shortest_path(self, s: int, t: int):
        """Scalar convenience mirroring ``ISLabelIndex.shortest_path``
        (used as the serving fallback for hop_cap overflows). Unlike
        the host-recursive oracle this runs the batched engine with
        escalating hop_cap — a finite distance with an empty path means
        the escalation ceiling was hit and no path was recovered."""
        dist, paths, ok = self.shortest_paths([s], [t])
        return float(dist[0]), paths[0]

    # --------------------------------------------------------- mutations
    def apply_mutations(self, ops):
        """§8.3 insert/delete batch over the partitioned label blocks.

        Functional: returns ``(new_index, info)`` and leaves this index
        untouched (callers re-register, e.g. through
        ``IndexRegistry.install``'s drain path). The shared host
        mutators (``repro.core.index``) run over the gathered label
        rows, then the change propagates *per touched row, per owning
        shard*: a block row is rewritten only where its kept-entry
        slice actually changed, so a delete of a shard-owned ancestor
        touches exactly that shard's block while mutated replicated
        (core-level) entries — every insert, since inserted vertices
        join the core — rebuild the touched rows of all blocks. Every
        other block row is bitwise-preserved (asserted in tests).

        The vertex→shard map keeps its original assignment (re-running
        ``assign_shards`` would reshuffle the round-robin ranks and
        spuriously migrate untouched entries); inserted vertices become
        core and are marked REPLICATED. The rebuilt
        ``ShardedQueryEngine`` compiles fresh entry points — sharded
        mutation is a swap-and-rewarm operation, not a zero-recompile
        one (docs/MUTATION.md).

        ``info``: {"touched_rows", "touched_shards", "inserted"}.
        """
        from types import SimpleNamespace

        from repro.core.index import apply_delete_host, apply_insert_host
        from repro.shard.partition import REPLICATED
        if self.up_ids is None:
            raise ValueError(
                "this ShardedIndex was saved without the up-edge "
                "matrices; §8.3 mutations need them — rebuild with "
                "ShardedIndex.from_index")
        ids_h, d_h, pred_h = self.gather_label_rows()
        st = SimpleNamespace(
            n=self.n, k=self.k, level=self.level.copy(),
            up_ids=self.up_ids, up_w=self.up_w,
            core_src=self.core_src.copy(), core_dst=self.core_dst.copy(),
            core_w=self.core_w.copy(), core_via=self.core_via.copy(),
            core_ids=self.core_ids.copy())
        shard_of = self.shard_of.copy()
        touched: set = set()
        inserted = []
        for op in ops:
            u = int(op.u)
            if op.kind == "insert":
                apply_insert_host(st, ids_h, d_h, pred_h, u,
                                  [int(v) for v in op.nbrs],
                                  [float(x) for x in op.ws], touched)
                shard_of[u] = REPLICATED        # u joined the core
                inserted.append(u)
            elif op.kind == "delete":
                apply_delete_host(st, ids_h, d_h, pred_h, u, touched)
            else:
                raise ValueError(f"unknown mutation kind {op.kind!r}")
        rows = np.asarray(sorted(touched), np.int64)

        blk_ids = np.asarray(self.lbl_ids).copy()
        blk_d = np.asarray(self.lbl_d).copy()
        blk_pred = self.lbl_pred.copy()
        entries = self.entries_per_shard.copy()
        cap = blk_ids.shape[2]
        touched_shards: set = set()
        for r in rows:
            valid = ids_h[r] < self.n
            owner = shard_of[np.minimum(ids_h[r], self.n)]
            for p in range(self.num_shards):
                # boolean-mask compaction keeps source order — the same
                # stable layout partition_labels produces
                keep = valid & ((owner == p) | (owner == REPLICATED))
                cnt = int(keep.sum())
                if cnt > cap:
                    raise RuntimeError(
                        f"shard {p} row {r}: {cnt} entries exceed the "
                        f"block cap {cap}; repartition the index")
                new_ids = np.full(cap, self.n, np.int32)
                new_d = np.full(cap, np.inf, np.float32)
                new_pred = np.full(cap, -1, np.int32)
                new_ids[:cnt] = ids_h[r][keep]
                new_d[:cnt] = d_h[r][keep]
                new_pred[:cnt] = pred_h[r][keep]
                if not (np.array_equal(blk_ids[p, r], new_ids)
                        and np.array_equal(blk_d[p, r], new_d)):
                    if r < self.n:
                        entries[p] += cnt - int(
                            (blk_ids[p, r] < self.n).sum())
                    blk_ids[p, r] = new_ids
                    blk_d[p, r] = new_d
                    blk_pred[p, r] = new_pred
                    touched_shards.add(p)
        core_ids = np.flatnonzero(st.level == self.k).astype(np.int32)
        core_pos = np.full(self.n + 1, len(core_ids), np.int32)
        core_pos[core_ids] = np.arange(len(core_ids), dtype=np.int32)
        stats = dataclasses.replace(
            self.stats, n_core=len(core_ids), m_core=len(st.core_src),
            label_entries=int((ids_h[:self.n] < self.n).sum()))
        new = ShardedIndex._assemble(
            n=self.n, k=self.k, cfg=self.cfg, level=st.level,
            shard_of=shard_of,
            blocks=LabelBlocks(ids=blk_ids, d=blk_d, pred=blk_pred,
                               entries=entries),
            core_ids=core_ids, core_pos=core_pos, core_src=st.core_src,
            core_dst=st.core_dst, core_w=st.core_w, stats=stats,
            strategy=self.strategy, replicate_top=self.replicate_top,
            mesh=self.mesh, core_via=st.core_via, up_ids=self.up_ids,
            up_w=self.up_w, up_via=self.up_via)
        info = {"touched_rows": rows,
                "touched_shards": sorted(touched_shards),
                "inserted": inserted}
        return new, info

    # ---------------------------------------------------------------- io
    def save(self, path) -> None:
        p = Path(path)
        p.mkdir(parents=True, exist_ok=True)
        path_state = {}
        if self.up_ids is not None:
            path_state = {"core_via": self.core_via, "up_ids": self.up_ids,
                          "up_w": self.up_w, "up_via": self.up_via}
        np.savez_compressed(
            p / "shards.npz", level=self.level, shard_of=self.shard_of,
            lbl_ids=np.asarray(self.lbl_ids), lbl_d=np.asarray(self.lbl_d),
            lbl_pred=np.asarray(self.lbl_pred), core_ids=self.core_ids,
            core_pos=self.core_pos_host, core_src=self.core_src,
            core_dst=self.core_dst, core_w=self.core_w, **path_state)
        meta = {"n": self.n, "k": self.k, "num_shards": self.num_shards,
                "strategy": self.strategy,
                "replicate_top": self.replicate_top,
                "cfg": dataclasses.asdict(self.cfg),
                "stats": dataclasses.asdict(self.stats)}
        (p / "meta.json").write_text(json.dumps(meta))

    @staticmethod
    def load(path, mesh: Mesh | None = None) -> "ShardedIndex":
        p = Path(path)
        meta = json.loads((p / "meta.json").read_text())
        z = np.load(p / "shards.npz")
        blocks = LabelBlocks(
            ids=z["lbl_ids"], d=z["lbl_d"], pred=z["lbl_pred"],
            entries=(z["lbl_ids"][:, :meta["n"]] < meta["n"])
            .sum(axis=(1, 2)).astype(np.int64))
        has_paths = "up_ids" in z.files
        idx = ShardedIndex._assemble(
            n=meta["n"], k=meta["k"], cfg=IndexConfig(**meta["cfg"]),
            level=z["level"], shard_of=z["shard_of"], blocks=blocks,
            core_ids=z["core_ids"], core_pos=z["core_pos"],
            core_src=z["core_src"], core_dst=z["core_dst"],
            core_w=z["core_w"], stats=BuildStats(**meta["stats"]),
            strategy=meta["strategy"], replicate_top=meta["replicate_top"],
            mesh=mesh,
            core_via=z["core_via"] if has_paths else None,
            up_ids=z["up_ids"] if has_paths else None,
            up_w=z["up_w"] if has_paths else None,
            up_via=z["up_via"] if has_paths else None)
        return idx
