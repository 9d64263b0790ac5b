"""The directed build cell's parts on the CPU: the generator's arcs, the
arc reorder, the directed reference, the in-label span reader, whole runs
of a tiny directed cell past the look for a chip, and a control that
answers with the symmetric shortcut."""
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(HERE))
import perfbench_tiny  # noqa: E402
from harness import trace  # noqa: E402
from harness.reference import Reference  # noqa: E402
from harness.reference_directed import DirectedReference  # noqa: E402
from harness.spec import load_module  # noqa: E402

KIND = load_module(BENCH / "kinds" / "build_directed.py",
                   "bench_kind_build_directed")
GEN = load_module(BENCH / "generators" / "kronecker_directed.py",
                  "bench_generator_kronecker_directed")
UNDIRECTED_GEN = load_module(BENCH / "generators" / "kronecker.py",
                             "bench_generator_kronecker")
IN_READER = load_module(BENCH / "metrics" / "build.label.in.device_s.py",
                        "bench_metric_build_label_in_device_s")

TINY_DIR = dict(perfbench_tiny.TINY_CONFIG, name="tiny-kron-dir",
                generator="kronecker_directed")
CELL = "tiny-kron-dir.build"
# the cell's per-layer metrics: the build's readers and the new one
PER_LAYER = ["build.peel_s", "build.label_s", "build.assemble_s",
             "build.label.slot_fill_pct", "build.peel.aug_fill_pct",
             "build.peel.device_s", "build.label.device_s",
             "build.label.in.device_s"]


def _graph(scale=10):
    return GEN.generate(TINY_DIR | {"scale": scale}, 1)


def test_generator_keeps_the_undirected_graphs_tuples():
    n, src, dst, w = _graph()
    keys = src.astype(np.int64) * n + dst
    assert (src != dst).all() and len(np.unique(keys)) == len(keys)
    assert set(np.unique(w)) <= {1.0, 2.0, 3.0, 4.0}
    rev = dst.astype(np.int64) * n + src
    assert 0 < np.isin(keys, rev).sum() < len(keys)     # some reciprocal
    # symmetrised, the arcs are the undirected generator's pairs
    _, us, ud, _ = UNDIRECTED_GEN.generate(TINY_DIR, 1)
    pairs = {(int(a), int(b)) for a, b in zip(us, ud)}
    assert pairs == ({(int(a), int(b)) for a, b in zip(src, dst)}
                     | {(int(b), int(a)) for a, b in zip(src, dst)})


def test_reorder_keeps_every_arc_and_its_orientation():
    g = _graph()
    h = KIND.reorder_arcs(g, np.random.default_rng(5))
    assert h[0] == g[0] and not np.array_equal(h[1], g[1])

    def arcs(x):
        return sorted(zip(x[1].tolist(), x[2].tolist(), x[3].tolist()))
    assert arcs(h) == arcs(g)


def test_sample_pairs_draw_tails_and_heads():
    g = _graph()
    s, t = KIND.sample_pairs(g, 2**31 + 17)
    assert len(s) == len(t) == KIND.SAMPLE
    assert np.isin(s, g[1]).all() and np.isin(t, g[2]).all()
    s2, t2 = KIND.sample_pairs(g, 2**31 + 17)
    assert np.array_equal(s, s2) and np.array_equal(t, t2)


def test_directed_reference_infinity_is_reachability():
    """0 -> 1 -> 2: three strongly connected components. 0 reaches 2, so
    an infinite answer is wrong there; 2 reaches nothing."""
    g = (4, np.array([0, 1]), np.array([1, 2]), np.array([1.0, 2.0]))
    ref = DirectedReference(g)
    assert not ref.check(0, 2, float("inf"))
    assert ref.check(0, 2, 3.0) and not ref.check(0, 2, 2.0)
    assert ref.check(2, 0, float("inf")) and not ref.check(2, 0, 3.0)
    assert ref.check(0, 3, float("inf"))
    # the undirected cells' reference takes components for reachability
    assert Reference(g).check(0, 2, float("inf"))


@pytest.mark.parametrize("seed", [0, 1])
def test_directed_reference_accepts_exactly_dijkstra(seed):
    from repro.core import ref as core_ref
    rng = np.random.default_rng(seed)
    n = 200
    src = rng.integers(0, n, 500)
    dst = rng.integers(0, n, 500)
    keep = src != dst
    g = (n, src[keep], dst[keep],
         rng.integers(1, 5, keep.sum()).astype(np.float32))
    ref = DirectedReference(g)
    s = rng.integers(0, n, 10)
    dist = core_ref.dijkstra_oracle(*g, s)
    for i, a in enumerate(s):
        for b in rng.integers(0, n, 10):
            d = float(dist[i, b])
            assert ref.check(a, b, d)
            if np.isfinite(d):
                assert not ref.check(a, b, d + 1)
                assert not ref.check(a, b, float("inf"))
            else:
                assert not ref.check(a, b, 5.0)


def _summary(label_in=True):
    """One 10 s build: label [4, 9] with the out family [4, 6] and the
    in family [6, 9]; the device busy [4.5, 5.5] and [6, 8]."""
    host = [(0.0, 10.0, "islabel.build"), (4.0, 9.0, "islabel.build.label")]
    if label_in:
        host += [(4.0, 6.0, "islabel.build.label.out"),
                 (6.0, 9.0, "islabel.build.label.in")]
    ops = [trace.Op(4.5, 5.5, "a", ""), trace.Op(6.0, 8.0, "b", "")]
    return trace.TraceSummary((0.0, 10.0), [ops], host)


def test_label_in_reader():
    layer = types.SimpleNamespace(trace=_summary(), build_stats=[], peak={})
    assert IN_READER.read(layer) == pytest.approx(2.0)
    # a program that opens no such span (an undirected build, or a
    # directed build without the family spans): nothing to read
    layer.trace = _summary(label_in=False)
    assert IN_READER.read(layer) is None
    cpu = trace.TraceSummary((0.0, 10.0), [], _summary().host_events)
    assert IN_READER.read(types.SimpleNamespace(trace=cpu)) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark plus a tiny directed configuration and cell."""
    root = perfbench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    (root / "bench" / "configs" / "tiny-kron-dir.json").write_text(
        json.dumps(TINY_DIR))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-kron-dir", "source": "test",
                             "file": "bench/configs/tiny-kron-dir.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-kron-dir",
                               "traffic": "build_directed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] not in PER_LAYER]
    bench["per_layer"] += [
        {"name": n, "unit": "%" if n.endswith("pct") else "s",
         "better": "lower", "source": "program_span", "layer": "build",
         "moves": "build_s", "workloads": [CELL]} for n in PER_LAYER]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_directed_cell_runs_and_is_correct(root, capsys):
    line = perfbench_tiny.run_cell(root, capsys, CELL)
    assert line["correct"] is True and line["attempted"] >= 1
    assert set(line["metrics"]) == {"build_s", "setup_s"}
    assert line["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    traced = perfbench_tiny.run_cell(root, capsys, CELL, trace=1)
    m = traced["metrics"]
    assert traced["correct"] is True
    # a CPU trace has no device plane: the device readers are left out
    assert set(m) == set(PER_LAYER) - {"build.peel.device_s",
                                       "build.label.device_s",
                                       "build.label.in.device_s"}
    for name in ("build.label.slot_fill_pct", "build.peel.aug_fill_pct"):
        assert 0.0 < m[name]["value"] <= 100.0


def test_symmetric_shortcut_is_caught():
    """A control that answers with out-labels on the t side and the
    forward core on both sides, as an undirected index would: the
    cell's check finds wrong answers where the program has none."""
    import jax.numpy as jnp
    from repro.core import IndexConfig
    from repro.core.directed import DiISLabelIndex
    from repro.core.query import QueryEngine
    g = KIND.reorder_arcs(_graph(), np.random.default_rng(3))
    idx = DiISLabelIndex.build(*g, IndexConfig(**TINY_DIR["index"]))
    eng = idx.engine
    shortcut = QueryEngine(idx.out_lbl[0], idx.out_lbl[1], eng.core_pos,
                           (eng.ce_src, eng.ce_dst, eng.ce_w), n=idx.n,
                           n_core=idx.n_core)
    control = types.SimpleNamespace(
        query=lambda s, t: shortcut.query(jnp.asarray(s), jnp.asarray(t)))
    assert KIND.count_wrong(idx, g, 7) == 0
    assert KIND.count_wrong(control, g, 7) > 0
