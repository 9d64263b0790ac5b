"""Tier-1 tests for the ``repro.shard`` subsystem (docs/SHARDING.md).

Covers: shard assignment invariants, partition round-trip (reassembled
labels == original), bitwise sharded-vs-unsharded query equality across
backends × shard counts {1, 2, 4} × strategies, the single-collective
guarantee, sharded save→load→serve, zero-compiles-after-warmup on the
sharded lane, a mixed sharded/unsharded registry, and the unsharded
engine fed batch-sharded queries on an 8-device mesh.

Multi-shard cases need >1 device: they run in subprocesses under
``--xla_force_host_platform_device_count=4`` (8 for the batch-sharded
case; this process must keep seeing the real 1-CPU world); the P=1
paths run in-process on the real device.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import ISLabelIndex, IndexConfig
from repro.graphs import generators as gen
from repro.shard import (REPLICATED, ShardedIndex, assign_shards,
                         partition_labels, unpartition_labels)

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_with_devices(code: str, n_dev: int = 4, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.fixture(scope="module")
def index():
    n, src, dst, w = gen.er_graph(400, 2.5, seed=5)
    return ISLabelIndex.build(n, src, dst, w,
                              IndexConfig(l_cap=128, label_chunk=128))


# ------------------------------------------------------------ assignment
@pytest.mark.parametrize("strategy", ["hash", "level"])
def test_assign_shards_invariants(index, strategy):
    so = assign_shards(index.level, index.k, 4, strategy=strategy)
    assert so.shape == (index.n + 1,) and so.dtype == np.int32
    # top level (the core) and the sentinel row are replicated
    assert np.all(so[:index.n][index.level == index.k] == REPLICATED)
    assert so[index.n] == REPLICATED
    movable = so[:index.n][index.level < index.k]
    assert movable.min(initial=0) >= 0 and movable.max(initial=0) < 4
    # deterministic
    again = assign_shards(index.level, index.k, 4, strategy=strategy)
    assert np.array_equal(so, again)


def test_assign_shards_level_strategy_balances_each_level(index):
    so = assign_shards(index.level, index.k, 2, strategy="level")
    for lv in np.unique(index.level[index.level < index.k]):
        counts = np.bincount(so[:index.n][index.level == lv], minlength=2)
        assert abs(int(counts[0]) - int(counts[1])) <= 1, (lv, counts)


def test_assign_shards_replicate_top_widens_replication(index):
    so = assign_shards(index.level, index.k, 2, replicate_top=index.k)
    assert np.all(so == REPLICATED)    # every level replicated


def test_assign_shards_rejects_bad_args(index):
    with pytest.raises(ValueError):
        assign_shards(index.level, index.k, 0)
    with pytest.raises(ValueError):
        assign_shards(index.level, index.k, 2, strategy="nope")
    with pytest.raises(ValueError):
        assign_shards(index.level, index.k, 2, replicate_top=0)


# ------------------------------------------------------- partition logic
@pytest.mark.parametrize("strategy", ["hash", "level"])
@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_partition_round_trip(index, strategy, num_shards):
    """unpartition(partition(labels)) == labels, bit for bit."""
    so = assign_shards(index.level, index.k, num_shards, strategy=strategy)
    blocks = partition_labels(index.lbl_ids, index.lbl_d, index.lbl_pred,
                              index.n, so, num_shards)
    assert blocks.ids.shape[0] == num_shards
    assert blocks.cap % 8 == 0
    ids, d, pred = unpartition_labels(blocks, index.n, index.cfg.l_cap)
    assert np.array_equal(ids, np.asarray(index.lbl_ids))
    assert np.array_equal(d, np.asarray(index.lbl_d))
    assert np.array_equal(pred, np.asarray(index.lbl_pred))


def test_partition_blocks_keep_rows_sorted_and_core_replicated(index):
    so = assign_shards(index.level, index.k, 2)
    blocks = partition_labels(index.lbl_ids, index.lbl_d, index.lbl_pred,
                              index.n, so, 2)
    core = set(np.flatnonzero(index.level == index.k).tolist())
    full = np.asarray(index.lbl_ids)
    for p in range(2):
        blk = blocks.ids[p]
        # id-sorted with the sentinel n padding the tail of each row
        assert np.all(np.diff(blk.astype(np.int64), axis=1) >= 0)
        # every core ancestor of every row is present in every shard
        for v in range(0, index.n, 37):
            row_core = {int(u) for u in full[v] if int(u) in core}
            blk_core = {int(u) for u in blk[v] if int(u) in core}
            assert row_core == blk_core, (p, v)


# ----------------------------------------- single device (P=1) in-process
def test_sharded_index_single_shard_bitwise(index):
    sidx = ShardedIndex.from_index(index, 1)
    r = np.random.default_rng(0)
    s = r.integers(0, index.n, 64).astype(np.int32)
    t = r.integers(0, index.n, 64).astype(np.int32)
    want_ans, want_rounds = index.engine.batch_fn()(s, t)
    got_ans, got_rounds = sidx.engine.batch_fn()(s, t)
    assert np.array_equal(np.asarray(got_ans), np.asarray(want_ans))
    assert int(got_rounds) == int(want_rounds)
    assert np.array_equal(np.asarray(sidx.engine.mu_batch_fn()(s, t)),
                          np.asarray(index.engine.mu_batch_fn()(s, t)))
    assert sidx.engine.collective_count() == 1


def test_sharded_index_save_load_round_trip(index, tmp_path):
    sidx = ShardedIndex.from_index(index, 1, strategy="hash")
    sidx.save(tmp_path / "sh")
    again = ShardedIndex.load(tmp_path / "sh")
    assert again.num_shards == 1 and again.strategy == "hash"
    assert np.array_equal(np.asarray(again.lbl_ids),
                          np.asarray(sidx.lbl_ids))
    r = np.random.default_rng(1)
    s = r.integers(0, index.n, 32).astype(np.int32)
    t = r.integers(0, index.n, 32).astype(np.int32)
    assert np.array_equal(np.asarray(again.query(s, t)),
                          np.asarray(index.query(s, t)))


def test_mesh_larger_than_devices_rejected(index):
    import jax
    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError):
        ShardedIndex.from_index(index, too_many)


# --------------------------------- multi-device (forced 4-CPU) subprocess
def test_sharded_query_bitwise_across_backends_and_shards():
    """ans/rounds/μ bitwise vs QueryEngine for P ∈ {1,2,4} × backends ×
    strategies, under forced 4-device CPU; exactly one collective."""
    out = run_with_devices("""
        import numpy as np
        from repro.core import ISLabelIndex, IndexConfig
        from repro.graphs import generators as gen
        from repro.shard import ShardedIndex
        n, src, dst, w = gen.er_graph(400, 2.5, seed=5)
        idx = ISLabelIndex.build(n, src, dst, w,
                                 IndexConfig(l_cap=128, label_chunk=128))
        r = np.random.default_rng(0)
        s = r.integers(0, n, 64).astype(np.int32)
        t = r.integers(0, n, 64).astype(np.int32)
        for backend in ("reference", "interpret"):
            want_ans, want_rounds = idx.engine.batch_fn(backend)(s, t)
            want_mu = idx.engine.mu_batch_fn(backend)(s, t)
            for strategy in ("level", "hash"):
                for P in (1, 2, 4):
                    sidx = ShardedIndex.from_index(idx, P, strategy=strategy)
                    ans, rounds = sidx.engine.batch_fn(backend)(s, t)
                    tag = (backend, strategy, P)
                    assert np.array_equal(np.asarray(ans),
                                          np.asarray(want_ans)), tag
                    assert int(rounds) == int(want_rounds), tag
                    mu = sidx.engine.mu_batch_fn(backend)(s, t)
                    assert np.array_equal(np.asarray(mu),
                                          np.asarray(want_mu)), tag
                    assert sidx.engine.collective_count(
                        backend=backend) == 1, tag
        print("ok")
    """)
    assert "ok" in out


def test_sharded_save_load_serve_and_zero_compiles():
    """save→load→DistanceServer over 4 shards: served answers bitwise ==
    the unsharded index, zero compiles after warmup on the sharded lane,
    and a registry hosts sharded + unsharded side by side."""
    out = run_with_devices("""
        import numpy as np, tempfile
        from repro.core import ISLabelIndex, IndexConfig
        from repro.graphs import generators as gen
        from repro.serve import DistanceServer, IndexRegistry, make_trace
        from repro.shard import ShardedIndex
        n, src, dst, w = gen.er_graph(400, 2.5, seed=5)
        idx = ISLabelIndex.build(n, src, dst, w,
                                 IndexConfig(l_cap=128, label_chunk=128))
        d = tempfile.mkdtemp()
        ShardedIndex.from_index(idx, 4).save(d)
        sidx = ShardedIndex.load(d)
        assert sidx.num_shards == 4
        srv = DistanceServer(sidx, buckets=(8, 32), max_wait_ms=1.0,
                             cache_size=4096)
        sizes = srv.compile_cache_sizes()
        tr = make_trace("hotspot", n=n, num_requests=300, rate_qps=2e4,
                        seed=4)
        got = srv.serve_trace(tr)
        want = np.asarray(idx.query(tr.s, tr.t), np.float32)
        assert np.array_equal(got, want)
        if -1 not in sizes.values():
            assert srv.compile_cache_sizes() == sizes   # zero new compiles
        assert srv.stats()["graph"]["shards"] == 4
        # mixed registry: sharded and unsharded side by side
        reg = IndexRegistry()
        reg.register("flat", idx, buckets=(8, 32), warmup=False)
        reg.register("sharded", sidx, buckets=(8, 32), warmup=False)
        tr2 = make_trace("uniform", n=n, num_requests=120, rate_qps=2e4,
                         seed=6)
        a = reg.get("flat").serve_trace(tr2)
        b = reg.get("sharded").serve_trace(tr2)
        assert np.array_equal(a, b)
        print("ok")
    """)
    assert "ok" in out


# ---------------------------------------- batch-sharded unsharded engine
def test_islabel_query_sharded_matches_local():
    """The unsharded query engine, fed queries batch-sharded over an
    8-device mesh, returns the same distances as on one device."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import ISLabelIndex, IndexConfig
        from repro.graphs import generators as gen
        n, src, dst, w = gen.er_graph(400, 3.0, seed=5)
        idx = ISLabelIndex.build(n, src, dst, w,
                                 IndexConfig(l_cap=128, label_chunk=128))
        r = np.random.default_rng(0)
        s = r.integers(0, n, 64).astype(np.int32)
        t = r.integers(0, n, 64).astype(np.int32)
        want = np.asarray(idx.query(s, t))
        # shard the queries across 8 devices; Auto axes let the
        # engine's single-device label gathers propagate the batch
        # sharding (Explicit axes would demand an out_sharding per gather)
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        sq = jax.device_put(jnp.asarray(s), NamedSharding(mesh, P("data")))
        tq = jax.device_put(jnp.asarray(t), NamedSharding(mesh, P("data")))
        got = np.asarray(idx.engine.query(sq, tq))
        fin = np.isfinite(want)
        assert (np.isfinite(got) == fin).all()
        np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5)
        print("ok")
    """, n_dev=8)
    assert "ok" in out
