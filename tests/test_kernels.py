"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle,
swept over shapes and dtypes. ``backend="interpret"`` is passed
explicitly: the wrappers' default resolves to the jnp reference off-TPU,
and these tests exist to exercise the Pallas program itself. hypothesis
is optional (requirements-dev); without it the property sweeps fall back
to fixed parametrized cases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.kernels.label_intersect.ops import label_intersect
from repro.kernels.label_intersect.ref import label_intersect_ref
from repro.kernels.minplus_matmul.ops import minplus_matmul
from repro.kernels.minplus_matmul.ref import minplus_matmul_ref
from repro.core.dispatch import ell_round
from repro.kernels.spmv_relax.ops import coo_to_ell
from repro.kernels.spmv_relax.ref import spmv_relax_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (128, 128, 128), (1, 1, 1),
                                   (100, 37, 250), (130, 260, 5),
                                   (256, 512, 128)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_minplus_shapes(m, k, n, dtype):
    a = RNG.random((m, k)).astype(dtype) * 10
    b = RNG.random((k, n)).astype(dtype) * 10
    a[RNG.random(a.shape) < 0.3] = np.inf        # sparse-as-inf pattern
    got = minplus_matmul(jnp.asarray(a), jnp.asarray(b), backend="interpret")
    want = minplus_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_minplus_block_shapes():
    a = RNG.random((64, 96)).astype(np.float32)
    b = RNG.random((96, 160)).astype(np.float32)
    for bm, bn, bk in [(32, 32, 32), (64, 128, 32), (16, 16, 96)]:
        got = minplus_matmul(jnp.asarray(a), jnp.asarray(b),
                             bm=bm, bn=bn, bk=bk, backend="interpret")
        want = minplus_matmul_ref(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


def test_minplus_is_apsp_step():
    """(min,+) self-product squares path lengths: two products give
    4-hop-exact distances on a small graph."""
    n = 24
    adj = np.full((n, n), np.inf, np.float32)
    np.fill_diagonal(adj, 0)
    for _ in range(60):
        a, b = RNG.integers(0, n, 2)
        w = float(RNG.integers(1, 5))
        adj[a, b] = min(adj[a, b], w)
        adj[b, a] = min(adj[b, a], w)
    d2 = np.asarray(minplus_matmul(jnp.asarray(adj), jnp.asarray(adj),
                                   backend="interpret"))
    d4 = np.asarray(minplus_matmul(jnp.asarray(d2), jnp.asarray(d2),
                                   backend="interpret"))
    import scipy.sparse.csgraph as csg
    import scipy.sparse as sp
    full = csg.shortest_path(sp.csr_matrix(np.where(np.isfinite(adj), adj, 0)))
    reach4 = full.copy()
    # d4 >= true distance, equal where hop-count <= 4
    fin = np.isfinite(d4)
    assert (d4[fin] >= full[fin] - 1e-4).all()


@pytest.mark.parametrize("q,l,n_sent", [(1, 8, 50), (37, 100, 1000),
                                        (64, 256, 10_000), (5, 513, 300)])
def test_label_intersect_shapes(q, l, n_sent):
    def rows():
        out = np.full((q, l), n_sent, np.int32)
        for i in range(q):
            sz = RNG.integers(1, min(l, n_sent) + 1)
            out[i, :sz] = np.sort(RNG.choice(n_sent, sz, replace=False))
        return out
    ids_s, ids_t = rows(), rows()
    d_s = (RNG.random((q, l)) * 9).astype(np.float32)
    d_t = (RNG.random((q, l)) * 9).astype(np.float32)
    got = np.asarray(label_intersect(
        jnp.asarray(ids_s), jnp.asarray(d_s), jnp.asarray(ids_t),
        jnp.asarray(d_t), n_sent, backend="interpret"))
    want = np.asarray(label_intersect_ref(
        jnp.asarray(ids_s), jnp.asarray(d_s), jnp.asarray(ids_t),
        jnp.asarray(d_t), n_sent))
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-6)


def _label_intersect_property_case(q, l, seed):
    r = np.random.default_rng(seed)
    n_sent = 200
    ids_s = np.sort(np.stack([r.choice(n_sent, l, replace=False)
                              for _ in range(q)])).astype(np.int32)
    ids_t = np.sort(np.stack([r.choice(n_sent, l, replace=False)
                              for _ in range(q)])).astype(np.int32)
    d_s = r.random((q, l)).astype(np.float32)
    d_t = r.random((q, l)).astype(np.float32)
    got = np.asarray(label_intersect(jnp.asarray(ids_s), jnp.asarray(d_s),
                                     jnp.asarray(ids_t), jnp.asarray(d_t),
                                     n_sent, backend="interpret"))
    want = np.asarray(label_intersect_ref(jnp.asarray(ids_s),
                                          jnp.asarray(d_s),
                                          jnp.asarray(ids_t),
                                          jnp.asarray(d_t), n_sent))
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-6)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(q=st.integers(1, 16), l=st.integers(1, 64), seed=st.integers(0, 99))
    def test_label_intersect_property(q, l, seed):
        _label_intersect_property_case(q, l, seed)
else:
    @pytest.mark.parametrize("q,l,seed", [(1, 1, 0), (3, 17, 1), (16, 64, 7),
                                          (5, 33, 42)])
    def test_label_intersect_property(q, l, seed):
        _label_intersect_property_case(q, l, seed)


def _ell_step(dist, ids, ws):
    """One XLA ELL round (stage-2 "ell_xla" route) on a row-major
    [Q, V] frontier."""
    return ell_round(jnp.asarray(dist).T, ids.T, ws.T).T


@pytest.mark.parametrize("v,e,q", [(20, 60, 3), (200, 900, 13),
                                   (513, 2000, 8)])
def test_ell_round_shapes(v, e, q):
    src = RNG.integers(0, v, e)
    dst = RNG.integers(0, v, e)
    w = RNG.integers(1, 5, e).astype(np.float32)
    ids, ws = coo_to_ell(v, src, dst, w)
    dist = np.full((q, v), np.inf, np.float32)
    dist[np.arange(q), RNG.integers(0, v, q)] = 0.0
    got = _ell_step(dist, ids, ws)
    want = spmv_relax_ref(jnp.asarray(dist), ids, ws)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ell_round_converges_to_sssp():
    """Iterating the XLA round converges to single-source distances."""
    from repro.core.ref import dijkstra_oracle
    v, e = 60, 200
    src = RNG.integers(0, v, e)
    dst = RNG.integers(0, v, e)
    w = RNG.integers(1, 5, e).astype(np.float32)
    ids, ws = coo_to_ell(v, src, dst, w)
    dist = np.full((4, v), np.inf, np.float32)
    srcs = [0, 5, 10, 20]
    dist[np.arange(4), srcs] = 0.0
    d = jnp.asarray(dist)
    for _ in range(v):
        d = _ell_step(d, ids, ws)
    # duplicate (src,dst) pairs must keep min weight — use the dedup
    # oracle (scipy's COO->CSR sums duplicates)
    want = dijkstra_oracle(v, src, dst, w, srcs)
    got = np.asarray(d)
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5)


def test_kernel_engine_equivalence():
    """The Pallas label_intersect kernel returns the same μ as the
    production engine's searchsorted path on a real index."""
    from repro.core import ISLabelIndex, IndexConfig
    from repro.core.query import label_intersect_mu
    from repro.graphs import generators as gen
    n, src, dst, w = gen.er_graph(200, 3.0, seed=31)
    idx = ISLabelIndex.build(n, src, dst, w,
                             IndexConfig(l_cap=128, label_chunk=64))
    r = np.random.default_rng(0)
    s = r.integers(0, n, 32).astype(np.int32)
    t = r.integers(0, n, 32).astype(np.int32)
    ids_s, d_s = idx.lbl_ids[s], idx.lbl_d[s]
    ids_t, d_t = idx.lbl_ids[t], idx.lbl_d[t]
    mu_engine, _ = label_intersect_mu(ids_s, d_s, ids_t, d_t, n, 128)
    mu_kernel = label_intersect(ids_s, d_s, ids_t, d_t, n,
                                backend="interpret")
    a, b = np.asarray(mu_engine), np.asarray(mu_kernel)
    fin = np.isfinite(a)
    assert (np.isfinite(b) == fin).all()
    np.testing.assert_allclose(a[fin], b[fin], rtol=1e-6)


# ------------------------------------------------ minplus inf-padding
def test_minplus_inf_padding_edges():
    """inf is the (min,+) additive zero: all-inf rows/cols (the exact
    shape padding the dispatch layer feeds the kernel) must survive
    bitwise — inf rows stay inf, finite results never contaminated."""
    m, k, n = 32, 48, 64
    a = (RNG.integers(1, 9, (m, k))).astype(np.float32)
    b = (RNG.integers(1, 9, (k, n))).astype(np.float32)
    a[5, :] = np.inf                      # unreachable source row
    a[:, 7] = np.inf                      # dead intermediate (a-side)
    b[7, :] = np.inf                      # dead intermediate (b-side)
    b[:, 9] = np.inf                      # unreachable target col
    a[11, :] = np.inf
    b[:, 11] = np.inf
    got = np.asarray(minplus_matmul(jnp.asarray(a), jnp.asarray(b),
                                    bm=8, bn=16, bk=16,
                                    backend="interpret"))
    want = np.asarray(minplus_matmul_ref(jnp.asarray(a), jnp.asarray(b)))
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    # integer weights: sums are exact, equality is bitwise
    np.testing.assert_array_equal(got[fin], want[fin])
    assert np.isinf(got[5]).all() and np.isinf(got[:, 9]).all()


def test_minplus_all_inf_block():
    a = np.full((16, 16), np.inf, np.float32)
    b = (RNG.integers(1, 9, (16, 16))).astype(np.float32)
    got = np.asarray(minplus_matmul(jnp.asarray(a), jnp.asarray(b),
                                    backend="interpret"))
    assert np.isinf(got).all()


# ------------------------------------------------ fused relax kernel
def _ell_graph(v, e, seed=0):
    r = np.random.default_rng(seed)
    src = r.integers(0, v, e)
    dst = r.integers(0, v, e)
    w = r.integers(1, 5, e).astype(np.float32)
    from repro.kernels.spmv_relax.ops import coo_to_ell as _c
    return _c(v, src, dst, w)


def test_fused_relax_matches_iterated_spmv():
    """One fused launch == the per-round spmv oracle run to its fixed
    point: bitwise distances AND the same round count (reported as the
    max over per-block in-kernel exit rounds)."""
    from repro.kernels.spmv_relax.kernel import fused_relax_kernel
    v, q = 128, 16
    ids, ws = _ell_graph(v, 400)
    dist = np.full((q, v), np.inf, np.float32)
    dist[np.arange(q), RNG.integers(0, v, q)] = 0.0
    dist[q - 1, :] = np.inf               # all-inf row settles immediately
    d = jnp.asarray(dist)
    rounds_loop = 0
    while True:
        d2 = spmv_relax_ref(d, ids, ws)
        rounds_loop += 1
        if bool(jnp.all(~(d2 < d))):
            d = d2
            break
        d = d2
        assert rounds_loop < v
    out, blk_rounds = fused_relax_kernel(jnp.asarray(dist), ids.T, ws.T,
                                         max_rounds=v, bq=8,
                                         interpret=True)
    got, want = np.asarray(out), np.asarray(d)
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    np.testing.assert_array_equal(got[fin], want[fin])
    assert int(np.max(np.asarray(blk_rounds))) == rounds_loop
    assert np.isinf(got[q - 1]).all()


def test_fused_relax_respects_max_rounds():
    """max_rounds truncates the fixed-point loop exactly like the
    per-round path: k fused rounds == k spmv oracle rounds."""
    from repro.kernels.spmv_relax.kernel import fused_relax_kernel
    v, q = 128, 8
    ids, ws = _ell_graph(v, 300, seed=3)
    dist = np.full((q, v), np.inf, np.float32)
    dist[np.arange(q), RNG.integers(0, v, q)] = 0.0
    d = jnp.asarray(dist)
    for _ in range(2):
        d = spmv_relax_ref(d, ids, ws)
    out, blk_rounds = fused_relax_kernel(jnp.asarray(dist), ids.T, ws.T,
                                         max_rounds=2, bq=8,
                                         interpret=True)
    got, want = np.asarray(out), np.asarray(d)
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    np.testing.assert_array_equal(got[fin], want[fin])
    assert int(np.max(np.asarray(blk_rounds))) <= 2


def test_fused_vmem_model_is_monotone():
    from repro.kernels.spmv_relax.kernel import fused_vmem_bytes
    assert fused_vmem_bytes(1024, 16) < fused_vmem_bytes(2048, 16)
    assert fused_vmem_bytes(1024, 16) < fused_vmem_bytes(1024, 32)
    # exact accounting, as the TPU compiler reports it: frontier in/out
    # (double-buffered) + carry scratch, ELL ids/w (buffered once),
    # rounds block (double-buffered)
    v, dw, bq = 512, 16, 8
    assert fused_vmem_bytes(v, dw, bq) == \
        4 * (5 * bq * v + 2 * v * dw + 2 * bq * 128)


# ----------------------------------------- packed (delta16) intersect
def test_label_intersect_packed_matches_plain():
    """Fused decode+join kernel == plain kernel on the decoded planes,
    bitwise, for both distance codecs (int32 integral / fp32 pass-
    through), including rows that are all pads."""
    from repro.core.labels import LabelRows, encode_labels
    from repro.kernels.label_intersect.ops import label_intersect_rows
    q, l, n = 24, 32, 5000
    r = np.random.default_rng(5)
    ids = (r.integers(0, 200, (q, 1))
           + np.cumsum(r.integers(1, 64, (q, l)), axis=1)).astype(np.int32)
    ids[::3, l - 5:] = n                 # pad tails
    ids[7, :] = n                        # fully padded row
    for d_plane in (r.integers(0, 50, (q, l)).astype(np.float32),
                    (r.random((q, l)) * 9).astype(np.float32)):
        d = np.where(ids < n, d_plane, np.inf).astype(np.float32)
        ids_t = np.roll(ids, 1, axis=0)
        d_t = np.roll(d, 1, axis=0)
        enc_s = encode_labels(ids, d, n)
        enc_t = encode_labels(ids_t, d_t, n)
        want = np.asarray(label_intersect(
            jnp.asarray(ids), jnp.asarray(d), jnp.asarray(ids_t),
            jnp.asarray(d_t), n, backend="interpret"))
        got = np.asarray(label_intersect_rows(
            LabelRows(*(jnp.asarray(x) for x in enc_s)),
            LabelRows(*(jnp.asarray(x) for x in enc_t)),
            n, codec="delta16", backend="interpret"))
        fin = np.isfinite(want)
        assert (np.isfinite(got) == fin).all()
        np.testing.assert_array_equal(got[fin], want[fin])
        assert np.isinf(got[7])
