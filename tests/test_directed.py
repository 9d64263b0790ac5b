"""Paper §8.2: directed graphs via in/out labels (+ the reachability
claim from the conclusion). hypothesis is optional (requirements-dev):
without it the property sweep falls back to fixed seeds."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core import IndexConfig, ref
from repro.core.directed import DiISLabelIndex


def _digraph(n, e, seed, maxw=5):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    keep = src != dst
    w = rng.integers(1, maxw, keep.sum()).astype(np.float32)
    return src[keep], dst[keep], w


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_directed_exact(seed):
    n = 180
    src, dst, w = _digraph(n, 700, seed)
    idx = DiISLabelIndex.build(n, src, dst, w,
                               IndexConfig(l_cap=256, label_chunk=128))
    rng = np.random.default_rng(seed + 100)
    s = rng.integers(0, n, 120).astype(np.int32)
    t = rng.integers(0, n, 120).astype(np.int32)
    got = idx.query_host(s, t)
    want = ref.dijkstra_oracle(n, src, dst, w, s)[np.arange(120), t]
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5)


def test_asymmetry_preserved():
    """dist(s->t) != dist(t->s) must be answered per direction."""
    # a directed cycle: 0->1->2->0 with distinct weights
    src = np.asarray([0, 1, 2], np.int32)
    dst = np.asarray([1, 2, 0], np.int32)
    w = np.asarray([1.0, 2.0, 4.0], np.float32)
    idx = DiISLabelIndex.build(3, src, dst, w,
                               IndexConfig(l_cap=16, label_chunk=8))
    assert float(idx.query_host([0], [1])[0]) == 1.0
    assert float(idx.query_host([1], [0])[0]) == 6.0


def test_reachability():
    """Directed IS-LABEL answers reachability (paper conclusion)."""
    # two directed chains with a one-way bridge
    src = np.asarray([0, 1, 5, 6, 2], np.int32)
    dst = np.asarray([1, 2, 6, 7, 5], np.int32)
    w = np.ones(5, np.float32)
    idx = DiISLabelIndex.build(8, src, dst, w,
                               IndexConfig(l_cap=16, label_chunk=8))
    assert idx.reachable([0], [7])[0]            # 0->1->2->5->6->7
    assert not idx.reachable([7], [0])[0]


def _directed_property_case(seed, n):
    src, dst, w = _digraph(n, n * 4, seed)
    if len(src) == 0:
        return
    idx = DiISLabelIndex.build(n, src, dst, w,
                               IndexConfig(l_cap=128, label_chunk=64,
                                           d_cap=8))
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, 30).astype(np.int32)
    t = rng.integers(0, n, 30).astype(np.int32)
    got = idx.query_host(s, t)
    want = ref.dijkstra_oracle(n, src, dst, w, s)[np.arange(30), t]
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 500), n=st.integers(20, 60))
    def test_directed_property(seed, n):
        _directed_property_case(seed, n)
else:
    @pytest.mark.parametrize("seed,n", [(0, 20), (17, 33), (101, 48),
                                        (404, 60)])
    def test_directed_property(seed, n):
        _directed_property_case(seed, n)


# ------------------------------------- device build and QueryEngine path
def _rmat_arcs(scale, edge_factor, seed, maxw=4):
    """Graph500 Kronecker (R-MAT) arcs, orientation kept, self-loops and
    duplicate arcs dropped, integral weights drawn per arc."""
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(scale):
        q = rng.random(m)
        src = (src << 1) | (q >= 0.76)
        dst = (dst << 1) | (((q >= 0.57) & (q < 0.76)) | (q >= 0.95))
    keep = src != dst
    keys = np.unique(src[keep] * n + dst[keep])
    w = rng.integers(1, maxw + 1, len(keys)).astype(np.float32)
    return n, (keys // n).astype(np.int32), (keys % n).astype(np.int32), w


def _exact(idx, n, src, dst, w, s, t):
    """Directed answers against Dijkstra, compared for equality
    (integral weights: sums are exact)."""
    got = idx.query_host(s, t)
    want = ref.dijkstra_oracle(n, src, dst, w, s)[np.arange(len(s)), t]
    np.testing.assert_array_equal(got, want.astype(np.float32))
    return got


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_directed_equals_oracle_with_asymmetric_and_unreachable_pairs(seed):
    n = 240
    src, dst, w = _digraph(n, 560, seed)
    idx = DiISLabelIndex.build(n, src, dst, w,
                               IndexConfig(l_cap=128, label_chunk=64))
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, 300).astype(np.int32)
    t = rng.integers(0, n, 300).astype(np.int32)
    # every pair asked both ways
    fwd = _exact(idx, n, src, dst, w, np.concatenate([s, t]),
                 np.concatenate([t, s]))
    there, back = fwd[:300], fwd[300:]
    assert np.isinf(fwd).any() and np.isfinite(fwd).any()
    assert (np.isfinite(there) != np.isfinite(back)).any()
    both = np.isfinite(there) & np.isfinite(back)
    assert (there[both] != back[both]).any()
    assert idx.n_core > 0 and idx.engine.relaxer.rev is not None


@pytest.mark.parametrize("scale", [8, 10])
def test_directed_kronecker_equals_oracle(scale):
    n, src, dst, w = _rmat_arcs(scale, 16, seed=scale)
    idx = DiISLabelIndex.build(n, src, dst, w, IndexConfig(l_cap=64))
    rng = np.random.default_rng(scale)
    tails = np.flatnonzero(np.bincount(src, minlength=n))
    heads = np.flatnonzero(np.bincount(dst, minlength=n))
    # keys as a directed search draws them, then any two vertices
    s = np.concatenate([rng.choice(tails, 200), rng.integers(0, n, 100)])
    t = np.concatenate([rng.choice(heads, 200), rng.integers(0, n, 100)])
    got = _exact(idx, n, src, dst, w, s.astype(np.int32),
                 t.astype(np.int32))
    assert np.isinf(got).any() and np.isfinite(got).any()
    assert idx.n_core > 0


@pytest.mark.parametrize("seed", [0, 1])
def test_undirected_graph_answers_alike_through_both_indexes(seed):
    """An undirected graph given to the directed index as both arcs of
    every edge answers bitwise what ``ISLabelIndex`` answers."""
    from repro.core import ISLabelIndex
    from repro.graphs import generators as gen
    n, src, dst, w = gen.er_graph(260, 3.0, seed=seed)
    cfg = IndexConfig(l_cap=128, label_chunk=64)
    und = ISLabelIndex.build(n, src, dst, w, cfg)
    di = DiISLabelIndex.build(n, src, dst, w, cfg)
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, 256).astype(np.int32)
    t = rng.integers(0, n, 256).astype(np.int32)
    a, b = und.query_host(s, t), di.query_host(s, t)
    assert a.tobytes() == b.tobytes()
    assert np.isfinite(a).any()


def test_directed_build_syncs_and_stats(peel_levels):
    """One blocking read per peeled level, and BuildStats counted over
    both label families."""
    n, src, dst, w = _rmat_arcs(9, 16, seed=1)
    cfg = IndexConfig(l_cap=64, label_chunk=128)
    idx = DiISLabelIndex.build(n, src, dst, w, cfg)
    st = idx.stats
    assert 0 < st.peel_loop_syncs <= st.peel_iters
    assert st.k == idx.k >= 2 and st.n == n and st.m == len(src)
    assert st.n_core == idx.n_core == int((idx.level == idx.k).sum())
    assert st.m_core == len(idx.core_host[0])
    assert st.host_syncs >= st.peel_loop_syncs + 3   # pull + two checks
    assert min(st.peel_seconds, st.label_seconds,
               st.assemble_seconds) > 0
    assert st.peel_seconds + st.label_seconds + st.assemble_seconds \
        <= st.build_seconds
    e_cap, aug_cap = cfg.e_cap(len(src)), cfg.aug_cap(len(src))
    assert st.peel_edge_slots == st.peel_iters * e_cap
    # one augmentation buffer per level, sized from its eligible-degree
    # bound counted edge by edge
    assert len(peel_levels) == st.peel_iters
    assert st.peel_aug_slots == sum(peel_levels.want(aug_cap))
    assert 0 < st.peel_aug_edges <= st.peel_aug_slots
    assert 0 < st.peel_edges <= st.peel_edge_slots
    # two label families over the same levels
    row_slots = cfg.d_cap * cfg.l_cap + 1
    chunk = cfg.label_chunk
    assert st.label_slots == 2 * sum(-(-s // chunk) * chunk * row_slots
                                     for s in st.level_sizes)
    rows = [(np.asarray(lbl[0]) < n).sum(1) for lbl in (idx.out_lbl,
                                                        idx.in_lbl)]
    assert st.label_entries == sum(int(r[:n].sum()) for r in rows)
    noncore = idx.level < idx.k
    want = sum(int(noncore.sum() + r[up[0][:n][noncore]].sum())
               for r, up in zip(rows, (idx.up_out, idx.up_in)))
    assert st.label_candidates == want
    # the second build of the same shapes compiles nothing
    again = DiISLabelIndex.build(n, src, dst, w, cfg).stats
    assert again.compiles == 0


def _cave_arcs(p_drop, seed=7):
    """Both arcs of every connected-caveman edge, less a random share
    ``p_drop`` of the arcs: a digraph that peels about ten levels, its
    eligible-degree bound falling on the way."""
    from repro.graphs import generators as gen
    n, src, dst, w = gen.caveman_graph(20, 10, seed=seed)
    drop = np.random.default_rng(seed).random(len(src)) < p_drop
    return n, src[~drop], dst[~drop], w[~drop]


def test_directed_level_aug_caps_follow_eligible_degree_bound(peel_levels):
    """Each level's augmentation buffer is its bound B_i, the out-degree
    sum over active vertices of in + out degree <= d_cap (recounted from
    the level's own arcs), rounded up to a power of two, at least 64,
    under the aug_cap_factor ceiling; the IS-incident arcs never exceed
    it."""
    from repro.core.hierarchy import build_hierarchy_device
    n, src, dst, w = _cave_arcs(0.2)
    cfg = IndexConfig()
    ceiling = cfg.aug_cap(len(src))
    h = build_hierarchy_device(n, src, dst, w, cfg, directed=True)
    assert h.peel_iters == len(peel_levels) >= 5
    assert h.aug_caps == peel_levels.caps() == peel_levels.want(ceiling)
    # the ceiling, several buckets and the floor of 64 all occur
    assert h.aug_caps[0] == ceiling and min(h.aug_caps) == 64
    assert len(set(h.aug_caps)) >= 4
    for cap, (_, bound), n_is_edges in zip(h.aug_caps, peel_levels,
                                           h.is_edges):
        assert n_is_edges <= bound
        assert n_is_edges <= cap


@pytest.mark.parametrize("name,mk", [
    ("cave", lambda: _cave_arcs(0.2)),
    ("rmat", lambda: _rmat_arcs(9, 8, seed=1))])
def test_directed_bucketed_buffer_bitwise_equals_full_cap(name, mk,
                                                          monkeypatch):
    """Shrinking the augmentation buffer to its bucket drops sentinel
    rows only: levels, up-edges, both label families and the answers
    equal those of a build whose every level runs the full ceiling."""
    from repro.core import hierarchy
    n, src, dst, w = mk()
    cfg = IndexConfig(l_cap=128, label_chunk=64)
    got = DiISLabelIndex.build(n, src, dst, w, cfg)
    monkeypatch.setattr(hierarchy, "level_aug_cap",
                        lambda bound, ceiling: ceiling)
    full = DiISLabelIndex.build(n, src, dst, w, cfg)
    assert full.stats.peel_aug_slots == \
        full.stats.peel_iters * cfg.aug_cap(len(src))
    assert got.stats.peel_aug_slots < full.stats.peel_aug_slots
    assert got.k == full.k
    np.testing.assert_array_equal(got.level, full.level)
    for a, b in zip((*got.up_out, *got.up_in, *got.out_lbl, *got.in_lbl,
                     *got.core_host),
                    (*full.up_out, *full.up_in, *full.out_lbl,
                     *full.in_lbl, *full.core_host)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rng = np.random.default_rng(0)
    s = rng.integers(0, n, 200).astype(np.int32)
    t = rng.integers(0, n, 200).astype(np.int32)
    ans = _exact(got, n, src, dst, w, s, t)
    assert ans.tobytes() == full.query_host(s, t).tobytes()


DIRECTED_SPANS = {
    "islabel.build": None,
    "islabel.build.peel": "islabel.build",
    "islabel.build.peel.level": "islabel.build.peel",
    "islabel.build.peel.pull": "islabel.build.peel",
    "islabel.build.label": "islabel.build",
    "islabel.build.label.out": "islabel.build.label",
    "islabel.build.label.in": "islabel.build.label",
    "islabel.build.label.check": "islabel.build.label",
    "islabel.build.assemble": "islabel.build",
}


def test_directed_build_spans(tmp_path):
    """A profiled directed build writes the undirected build's spans,
    each label family under its own child of ``islabel.build.label``."""
    import jax
    from jax.profiler import ProfileData
    n, src, dst, w = _rmat_arcs(8, 8, seed=2)
    cfg = IndexConfig(l_cap=64, label_chunk=64)
    DiISLabelIndex.build(n, src, dst, w, cfg)          # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        idx = DiISLabelIndex.build(n, src, dst, w, cfg)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#")[0]
                if name.startswith("islabel."):
                    events.setdefault(name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert set(DIRECTED_SPANS) | {"islabel.sync"} <= set(events)
    for name in ("islabel.build", "islabel.build.label.out",
                 "islabel.build.label.in"):
        assert len(events[name]) == 1
    (out_s, out_e), = events["islabel.build.label.out"]
    (in_s, _), = events["islabel.build.label.in"]
    assert out_e <= in_s
    assert len(events["islabel.build.peel.level"]) == idx.stats.peel_iters
    for child, parent in DIRECTED_SPANS.items():
        if parent is None:
            continue
        for s, e in events[child]:
            assert any(ps <= s and e <= pe for ps, pe in events[parent])
    assert len(events["islabel.sync"]) == idx.stats.host_syncs


@pytest.mark.parametrize("cfg,match", [
    (IndexConfig(e_cap_factor=1.2, aug_cap_factor=8.0, d_cap=16),
     r"edge capacity overflow at level 1: \d+ > \d+; raise "
     r"IndexConfig.e_cap_factor"),
    (IndexConfig(e_cap_factor=8.0, aug_cap_factor=0.05, d_cap=16),
     r"augmentation buffer overflow at level 1: \d+ > \d+; raise "
     r"IndexConfig.aug_cap_factor"),
])
def test_directed_overflow_names_level_and_cap(cfg, match):
    """Augmentation outpaces the removals on a dense random digraph: the
    level's stats read raises, naming the level and the cap."""
    src, dst, w = _digraph(300, 1800, seed=3)
    with pytest.raises(RuntimeError, match=match):
        DiISLabelIndex.build(300, src, dst, w, cfg)


def _random_core(v, m, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, m).astype(np.int32)
    dst = rng.integers(0, v, m).astype(np.int32)
    keep = src != dst
    return (src[keep], dst[keep],
            rng.integers(1, 5, keep.sum()).astype(np.float32))


@pytest.mark.parametrize("route", ["dense", "fused", "ell_xla"])
def test_directed_core_routes_match_reference(route):
    """Each kernel route relaxes the t-side over the reversed arcs and
    answers bitwise what the COO reference answers."""
    import jax.numpy as jnp
    from repro.core.dispatch import CoreRelaxer
    v, q = 40, 6
    es, ed, ew = (jnp.asarray(a) for a in _random_core(v, 90, seed=7))
    kw = {"dense": {}, "fused": {"dense_threshold": 2.0},
          "ell_xla": {"dense_threshold": 2.0, "fused": False}}[route]
    relaxer = CoreRelaxer(es, ed, ew, v, rev=(ed, es, ew), **kw)
    assert relaxer.mode == route
    rng = np.random.default_rng(1)
    seed_s = np.full((q, v + 1), np.inf, np.float32)
    seed_t = np.full((q, v + 1), np.inf, np.float32)
    seed_s[np.arange(q), rng.integers(0, v, q)] = rng.integers(0, 3, q)
    seed_t[np.arange(q), rng.integers(0, v, q)] = rng.integers(0, 3, q)
    mu = jnp.full((q,), jnp.inf, jnp.float32)
    args = (jnp.asarray(seed_s), jnp.asarray(seed_t), mu, v)
    a_ref, ds_r, dt_r, _ = relaxer.run(*args, backend="reference")
    a_k, ds_k, dt_k, _ = relaxer.run(*args, backend="interpret")
    for x, y in ((a_ref, a_k), (ds_r, ds_k), (dt_r, dt_k)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the t side really ran backwards: Dijkstra over the reversed arcs
    t0 = int(np.flatnonzero(np.isfinite(seed_t[0]))[0])
    back = ref.dijkstra_oracle(v, np.asarray(ed), np.asarray(es),
                               np.asarray(ew), np.asarray([t0]))[0]
    np.testing.assert_array_equal(np.asarray(dt_r)[0, :v],
                                  back + seed_t[0, t0])


def test_directed_interpret_kernels_end_to_end():
    """Stage 1's intersect kernel and stage 2's kernel route, in
    interpret mode, answer what the reference backend answers."""
    n = 120
    src, dst, w = _digraph(n, 420, seed=9)
    idx = DiISLabelIndex.build(n, src, dst, w,
                               IndexConfig(l_cap=64, label_chunk=64))
    rng = np.random.default_rng(9)
    s = rng.integers(0, n, 24).astype(np.int32)
    t = rng.integers(0, n, 24).astype(np.int32)
    a = np.asarray(idx.engine.query(s, t, backend="reference"))
    b = np.asarray(idx.engine.query(s, t, backend="interpret"))
    np.testing.assert_array_equal(a, b)
    want = ref.dijkstra_oracle(n, src, dst, w, s)[np.arange(24), t]
    np.testing.assert_array_equal(a, want.astype(np.float32))
