"""Kernel dispatch layer: backend resolution, Pallas-vs-reference parity
on random graphs, and end-to-end regression of QueryEngine answers
against the core/ref.py Dijkstra oracle across backends and chunking."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import ISLabelIndex, IndexConfig, ref
from repro.core.dispatch import FUSED_MAX_V, CoreRelaxer, core_relax
from repro.graphs import generators as gen
from repro.kernels.backend import ENV_VAR, resolve_backend

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def small_index():
    n, src, dst, w = gen.er_graph(260, 3.0, seed=11)
    idx = ISLabelIndex.build(n, src, dst, w,
                             IndexConfig(l_cap=128, label_chunk=64))
    assert idx.stats.n_core > 0          # stage 2 must actually run
    s = RNG.integers(0, n, 96).astype(np.int32)
    t = RNG.integers(0, n, 96).astype(np.int32)
    want = ref.dijkstra_oracle(n, src, dst, w, s)[np.arange(96), t]
    return idx, s, t, want


def _assert_same(got, want, rtol=0.0):
    got, want = np.asarray(got), np.asarray(want)
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    if rtol:
        np.testing.assert_allclose(got[fin], want[fin], rtol=rtol)
    else:
        np.testing.assert_array_equal(got[fin], want[fin].astype(np.float32))


# ------------------------------------------------------------ resolution
def test_resolve_backend_explicit():
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("interpret") == "interpret"
    assert resolve_backend("reference") == "reference"
    with pytest.raises(ValueError):
        resolve_backend("cuda")


def test_resolve_backend_env(monkeypatch):
    monkeypatch.setenv(ENV_VAR, "interpret")
    assert resolve_backend(None) == "interpret"
    assert resolve_backend("auto") == "interpret"
    # explicit request still beats the env override
    assert resolve_backend("reference") == "reference"
    monkeypatch.delenv(ENV_VAR)
    assert resolve_backend(None) in ("pallas", "reference")


# ------------------------------------------------- stage-wise parity
def test_mu_backend_parity(small_index):
    idx, s, t, _ = small_index
    mu_ref = idx.engine.query_mu_only(s, t, backend="reference")
    mu_ker = idx.engine.query_mu_only(s, t, backend="interpret")
    assert np.array_equal(np.asarray(mu_ref), np.asarray(mu_ker))


def test_core_relaxer_matches_reference_relax(small_index):
    """CoreRelaxer kernel path == legacy COO core_relax on real seeds."""
    idx, s, t, _ = small_index
    eng = idx.engine
    ids_s, d_s = eng.lbl_ids[jnp.asarray(s)], eng.lbl_d[jnp.asarray(s)]
    ids_t, d_t = eng.lbl_ids[jnp.asarray(t)], eng.lbl_d[jnp.asarray(t)]
    seed_s, seed_t = eng._seed(ids_s, d_s), eng._seed(ids_t, d_t)
    mu = eng.query_mu_only(s, t, backend="reference")
    a_ref, ds_r, dt_r, r_ref = core_relax(
        seed_s, seed_t, eng.ce_src, eng.ce_dst, eng.ce_w, mu,
        eng.n_core, eng.max_rounds)
    a_ker, ds_k, dt_k, r_ker = eng.relaxer.run(
        seed_s, seed_t, mu, eng.max_rounds, backend="interpret")
    assert int(r_ref) == int(r_ker)
    for a, b in ((a_ref, a_ker), (ds_r, ds_k), (dt_r, dt_k)):
        a, b = np.asarray(a), np.asarray(b)
        fin = np.isfinite(a)
        assert (np.isfinite(b) == fin).all()
        np.testing.assert_array_equal(a[fin], b[fin])


def test_relaxer_on_random_graphs():
    """Pallas interpret vs jnp reference relaxation on raw random cores."""
    for seed in (0, 3):
        r = np.random.default_rng(seed)
        v, e, q = 97, 400, 13
        ce_s = jnp.asarray(r.integers(0, v, e).astype(np.int32))
        ce_d = jnp.asarray(r.integers(0, v, e).astype(np.int32))
        ce_w = jnp.asarray(r.integers(1, 5, e).astype(np.float32))
        relaxer = CoreRelaxer(ce_s, ce_d, ce_w, v)
        seed_s = np.full((q, v + 1), np.inf, np.float32)
        seed_t = np.full((q, v + 1), np.inf, np.float32)
        seed_s[np.arange(q), r.integers(0, v, q)] = 0.0
        seed_t[np.arange(q), r.integers(0, v, q)] = 0.0
        mu = jnp.full((q,), jnp.inf, jnp.float32)
        a_ref, *_ = relaxer.run(jnp.asarray(seed_s), jnp.asarray(seed_t),
                                mu, v, backend="reference")
        a_ker, *_ = relaxer.run(jnp.asarray(seed_s), jnp.asarray(seed_t),
                                mu, v, backend="interpret")
        _assert_same(np.asarray(a_ker), np.asarray(a_ref))


# ------------------------------------- end-to-end regression vs Dijkstra
@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_query_matches_dijkstra(small_index, backend):
    idx, s, t, want = small_index
    got = idx.engine.query(s, t, backend=backend)
    _assert_same(got, want, rtol=1e-5)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_chunked_equals_unchunked(small_index, backend):
    idx, s, t, _ = small_index
    full = np.asarray(idx.engine.query(s, t, backend=backend))
    # 96 queries, chunk 37 -> two full chunks + padded tail
    chunked = np.asarray(idx.engine.query(s, t, backend=backend,
                                          query_chunk=37))
    assert np.array_equal(np.nan_to_num(full, posinf=-1.0),
                          np.nan_to_num(chunked, posinf=-1.0))


def test_config_chunk_and_backend_plumbed():
    """query_backend/query_chunk reach the engine through IndexConfig and
    survive save/load."""
    n, src, dst, w = gen.er_graph(140, 3.0, seed=4)
    cfg = IndexConfig(l_cap=128, label_chunk=64, query_backend="reference",
                      query_chunk=19)
    idx = ISLabelIndex.build(n, src, dst, w, cfg)
    assert idx.engine.backend == "reference"
    assert idx.engine.query_chunk == 19
    s = RNG.integers(0, n, 50).astype(np.int32)
    t = RNG.integers(0, n, 50).astype(np.int32)
    got = np.asarray(idx.query(s, t))
    want = ref.dijkstra_oracle(n, src, dst, w, s)[np.arange(50), t]
    _assert_same(got, want, rtol=1e-5)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        idx.save(d)
        idx2 = ISLabelIndex.load(d)
        assert idx2.engine.query_chunk == 19
        assert np.array_equal(np.nan_to_num(np.asarray(idx2.query(s, t)),
                                            posinf=-1.0),
                              np.nan_to_num(got, posinf=-1.0))


# ----------------------------------------------- kernel-route selection
def _random_core(v, e, seed=0):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.integers(0, v, e).astype(np.int32)),
            jnp.asarray(r.integers(0, v, e).astype(np.int32)),
            jnp.asarray(r.integers(1, 5, e).astype(np.float32)))


def test_dispatch_density_routing():
    """Route selection: density >= threshold with a small core picks the
    minplus dense route; sparse cores pick the fused kernel; the VMEM
    budget, the core-size cap and the fused kill-switch all send the
    core to the XLA gather round."""
    v = 100
    dense_edges = _random_core(v, int(0.1 * v * v))
    sparse_edges = _random_core(v, 2 * v, seed=1)
    assert CoreRelaxer(*dense_edges, v).mode == "dense"
    assert CoreRelaxer(*sparse_edges, v).mode == "fused"
    # threshold raised above the actual density -> no dense route
    assert CoreRelaxer(*dense_edges, v,
                       dense_threshold=0.5).mode == "fused"
    # core too big for the dense route even when dense enough
    assert CoreRelaxer(*dense_edges, v, dense_cap=50).mode == "fused"
    # fused kill-switch -> XLA gather round
    assert CoreRelaxer(*sparse_edges, v, fused=False,
                       dense_threshold=2.0).mode == "ell_xla"
    # fused working set over the VMEM budget -> XLA gather round
    assert CoreRelaxer(*sparse_edges, v, dense_threshold=2.0,
                       vmem_budget=1).mode == "ell_xla"
    # core above the fused kernel's size cap -> XLA gather round
    big = FUSED_MAX_V + 1
    assert CoreRelaxer(*_random_core(big, 2 * big, seed=4),
                       big).mode == "ell_xla"


def test_dispatch_env_overrides(monkeypatch):
    v = 100
    dense_edges = _random_core(v, int(0.1 * v * v))
    monkeypatch.setenv("ISLABEL_FUSED_RELAX", "0")
    monkeypatch.setenv("ISLABEL_DENSE_THRESHOLD", "0.5")
    assert CoreRelaxer(*dense_edges, v).mode == "ell_xla"
    monkeypatch.delenv("ISLABEL_DENSE_THRESHOLD")
    monkeypatch.delenv("ISLABEL_FUSED_RELAX")
    assert CoreRelaxer(*dense_edges, v).mode == "dense"


@pytest.mark.parametrize("force", ["dense", "fused", "ell_xla"])
def test_all_kernel_routes_bitwise_equal_reference(force):
    """Every stage-2 route (dense minplus GEMM, fused all-rounds kernel,
    XLA gather round) == the COO reference bitwise, with the same round
    count."""
    v, e, q = 120, 1450, 9           # density ~0.1: dense-eligible
    edges = _random_core(v, e, seed=2)
    kw = {"dense": dict(),
          "fused": dict(dense_threshold=2.0),
          "ell_xla": dict(dense_threshold=2.0, fused=False)}[force]
    relaxer = CoreRelaxer(*edges, v, **kw)
    assert relaxer.mode == force
    r = np.random.default_rng(3)
    seed_s = np.full((q, v + 1), np.inf, np.float32)
    seed_t = np.full((q, v + 1), np.inf, np.float32)
    seed_s[np.arange(q), r.integers(0, v, q)] = 0.0
    seed_t[np.arange(q), r.integers(0, v, q)] = 0.0
    seed_s[q - 1, :] = np.inf            # empty frontier row
    mu = jnp.full((q,), jnp.inf, jnp.float32)
    a_ref, ds_r, dt_r, r_ref = relaxer.run(
        jnp.asarray(seed_s), jnp.asarray(seed_t), mu, v,
        backend="reference")
    a_k, ds_k, dt_k, r_k = relaxer.run(
        jnp.asarray(seed_s), jnp.asarray(seed_t), mu, v,
        backend="interpret")
    assert int(r_ref) == int(r_k)
    for a, b in ((a_ref, a_k), (ds_r, ds_k), (dt_r, dt_k)):
        _assert_same(b, np.asarray(a))
    assert np.isinf(np.asarray(ds_k)[q - 1]).all()
