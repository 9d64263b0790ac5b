"""`ShardedQueryEngine` — Algorithm 1 over P label partitions.

Per batch, every shard runs both stages on its own block through the
same kernel dispatch layer the unsharded `QueryEngine` uses:

  stage 1  μ_p = Equation 1 over the shard's label block
           (``label_intersect_dispatch``). Ancestor-partitioned blocks
           make every (s, t) match shard-local, so μ = min_p μ_p.
  stage 2  the label-seeded core relaxation, shard-locally: the top
           hierarchy levels are replicated into every block
           (partition.py), so each shard scatters the *complete* core
           seed frontier and relaxes G_k to the identical fixed point —
           bit-for-bit the unsharded ds/dt (the sentinel column may
           hold different parked non-core entries per shard, but no
           core edge reads or writes it and ``through_core`` excludes
           it).

  answer   ans_p = min(μ_p, through_core); one ``lax.pmin`` over the
           mesh's shard axis — the batch's single collective — yields
           min_p ans_p = min(μ, through_core) = ``QueryEngine.batch_fn``
           bitwise (float min is exact under any grouping). ``rounds``
           is identical on every shard (same seeds, same rounds), so it
           leaves the shard_map as a replicated output, not a second
           collective.

Serving contract mirrors `QueryEngine`: ``batch_fn``/``mu_batch_fn``
return jitted fixed-shape callables memoized per resolved backend with
no host sync inside, and ``warmup`` pre-compiles every batch size so
the serving path never triggers XLA compilation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.dispatch import (CoreRelaxer,
                                 label_intersect_rows_dispatch)
from repro.core.labels import LabelRows, decode_rows
from repro.core.query import QueryEngine
from repro.kernels.backend import resolve_backend
from repro.obs.registry import REGISTRY

__all__ = ["ShardedQueryEngine"]


class ShardedQueryEngine:
    """Device-resident sharded query state + compiled entry points.

    ``lbl_ids``/``lbl_d``: [P, n+1, cap_s] blocks laid out over the
    mesh's ``shard`` axis (one partition per device slice); core state
    (``core_pos`` and the local-index COO edges) replicated.

    ``enc``/``codec``: compressed label planes (``repro.core.labels``
    delta16) sharded identically — per-shard blocks encode row-locally,
    so each shard decodes its own block in-kernel and the pmin'd answer
    stays bitwise-equal to the unsharded engine.
    """

    def __init__(self, lbl_ids, lbl_d, core_pos, core_local_edges, n: int,
                 n_core: int, mesh, max_rounds: int = 0,
                 backend: str = "auto", enc=None, codec: str = "none"):
        self.lbl_ids = lbl_ids
        self.lbl_d = lbl_d
        self.core_pos = core_pos
        self.ce_src, self.ce_dst, self.ce_w = core_local_edges
        self.n = n
        self.n_core = n_core
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.num_shards = mesh.shape[self.axis]
        self.cap = lbl_ids.shape[2]
        self.max_rounds = max_rounds if max_rounds > 0 else max(n_core, 1)
        self.backend = backend
        self.codec = codec
        if codec == "none":
            self.enc_ids, self.enc_base, self.enc_d = lbl_ids, None, lbl_d
        else:
            self.enc_ids, self.enc_base, self.enc_d = enc
        self.relaxer = CoreRelaxer(self.ce_src, self.ce_dst, self.ce_w,
                                   n_core) if n_core > 0 else None
        self._batch_fns: dict = {}
        self._mu_batch_fns: dict = {}

    # ------------------------------------------------------ shard-local
    # The unsharded seed scatter applied to one shard's label rows
    # yields a frontier identical on every shard in the real columns
    # (core ancestors are replicated into every block); non-core
    # entries park in the sentinel column n_core, which stage 2
    # ignores. Shared with QueryEngine so the bitwise contract cannot
    # drift between the twins.
    _seed = QueryEngine._seed

    def _shard_block(self, blk: LabelRows, s, t, backend: str,
                     mu_only: bool):
        """Both stages on one shard's block (``blk``: the shard's label
        planes in the active codec). Runs inside shard_map; the only
        collective is the final pmin over the shard axis."""
        with jax.named_scope("islabel.shard_block"):
            rows_s = LabelRows(
                blk.ids[s], None if blk.base is None else blk.base[s],
                blk.d[s])
            rows_t = LabelRows(
                blk.ids[t], None if blk.base is None else blk.base[t],
                blk.d[t])
            mu = label_intersect_rows_dispatch(rows_s, rows_t, self.n,
                                               self.codec, backend)
            if mu_only:
                return jax.lax.pmin(mu, self.axis)
            if self.n_core == 0:
                return jax.lax.pmin(mu, self.axis), jnp.int32(0)
            ids_s, d_s = decode_rows(rows_s, self.n, self.codec)
            ids_t, d_t = decode_rows(rows_t, self.n, self.codec)
            seed_s = self._seed(ids_s, d_s)
            seed_t = self._seed(ids_t, d_t)
            ans, _, _, rounds = self.relaxer.run(seed_s, seed_t, mu,
                                                 self.max_rounds, backend)
            return jax.lax.pmin(ans, self.axis), rounds

    def _make_fn(self, backend: str, mu_only: bool):
        blocks = P(self.axis, None, None)
        out_specs = P() if mu_only else (P(), P())

        # rounds is bitwise-identical across shards (identical seeds in
        # the real columns -> identical relaxation), so out_spec P()
        # with check_vma=False just adopts the replicated value.
        if self.codec == "none":
            def shard_fn(blk_ids, blk_d, s, t):
                # the per-device block keeps a leading axis of size 1
                return self._shard_block(
                    LabelRows(blk_ids[0], None, blk_d[0]), s, t,
                    backend, mu_only)

            mapped = jax.shard_map(shard_fn, mesh=self.mesh,
                                   in_specs=(blocks, blocks, P(), P()),
                                   out_specs=out_specs, check_vma=False)

            def run(s, t):
                return mapped(self.lbl_ids, self.lbl_d,
                              jnp.asarray(s, jnp.int32),
                              jnp.asarray(t, jnp.int32))
        else:
            base_blocks = P(self.axis, None)

            def shard_fn(blk_ids, blk_base, blk_d, s, t):
                return self._shard_block(
                    LabelRows(blk_ids[0], blk_base[0], blk_d[0]), s, t,
                    backend, mu_only)

            mapped = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(blocks, base_blocks, blocks, P(), P()),
                out_specs=out_specs, check_vma=False)

            def run(s, t):
                return mapped(self.enc_ids, self.enc_base, self.enc_d,
                              jnp.asarray(s, jnp.int32),
                              jnp.asarray(t, jnp.int32))
        return self._counted(jax.jit(run), "mu" if mu_only else "full")

    def _counted(self, fn, path: str):
        """Host-side dispatch counter around a jitted entry point:
        ``shard.batches{path,shards}`` in the process registry. The jit
        ``_cache_size`` probe is forwarded so the zero-compile audits
        (``DistanceServer.compile_cache_sizes``) see through the wrap."""
        calls = REGISTRY.counter("shard.batches",
                                 "sharded batch dispatches")
        labels = {"path": path, "shards": str(self.num_shards)}

        def run(s, t):
            calls.inc(1, **labels)
            return fn(s, t)

        if hasattr(fn, "_cache_size"):
            run._cache_size = fn._cache_size
        run.__wrapped__ = fn
        return run

    # ------------------------------------------------------- serving APIs
    def batch_fn(self, backend: str | None = None):
        """Jitted ``run(s, t) -> (ans float32[Q], rounds int32 scalar)``
        — the sharded twin of ``QueryEngine.batch_fn`` (bitwise-equal
        answers), memoized per resolved backend."""
        backend = resolve_backend(self.backend if backend is None else backend)
        if backend not in self._batch_fns:
            self._batch_fns[backend] = self._make_fn(backend, mu_only=False)
        return self._batch_fns[backend]

    def mu_batch_fn(self, backend: str | None = None):
        """Jitted Equation-1-only ``run(s, t) -> ans float32[Q]`` — the
        μ-exact routed lane, sharded (per-shard partial μ + one pmin)."""
        backend = resolve_backend(self.backend if backend is None else backend)
        if backend not in self._mu_batch_fns:
            self._mu_batch_fns[backend] = self._make_fn(backend, mu_only=True)
        return self._mu_batch_fns[backend]

    def query(self, s, t, backend: str | None = None):
        """Batched distances (compiles per distinct batch shape; serving
        goes through the pre-warmed bucketed ``batch_fn`` instead)."""
        ans, _ = self.batch_fn(backend)(s, t)
        return ans

    def query_mu_only(self, s, t, backend: str | None = None):
        return self.mu_batch_fn(backend)(s, t)

    # warmup pre-compiles the *sharded* entry points per batch size
    # (same contract, same {(path, size): seconds} report); classify
    # reads no engine state — both reuse the QueryEngine logic.
    warmup = QueryEngine.warmup
    classify = QueryEngine.classify

    def collective_count(self, batch_size: int = 8,
                         backend: str | None = None) -> int:
        """Number of cross-shard collectives in one full-path batch —
        asserted to be exactly 1 in tests (the closed-jaxpr pmin count;
        no per-shard host round trips by construction)."""
        fn = self.batch_fn(backend)
        z = jnp.zeros(int(batch_size), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda s, t: fn(s, t))(z, z)
        text = str(jaxpr)
        count = sum(text.count(f"{prim}[")
                    for prim in ("pmin", "pmax", "psum"))
        REGISTRY.gauge("shard.collectives_per_batch",
                       "cross-shard collectives per full-path batch").set(
            count, shards=str(self.num_shards))
        return count
