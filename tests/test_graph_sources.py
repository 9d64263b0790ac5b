"""Tier-1 tests for the graph sources in ``repro.graphs.generators``:
the SNAP edge-list loader/saver and the ``graph_from_spec`` front door
(docs/CONSTRUCTION.md §1)."""
from __future__ import annotations

import numpy as np
import pytest

from repro.graphs import generators as gen


def edge_weights(src, dst, w):
    """Canonical (lo < hi) edge -> weight."""
    return {(int(u), int(v)): float(x)
            for u, v, x in zip(src, dst, w) if u < v}


def write(tmp_path, text):
    path = tmp_path / "g.txt"
    path.write_text(text)
    return path


@pytest.mark.parametrize("weighted", [False, True])
def test_snap_round_trip(tmp_path, weighted):
    n, src, dst, w = gen.grid_graph(6, seed=2)     # no isolated vertices
    if not weighted:
        n, src, dst, w = gen.unit_weights(n, src, dst, w)
    path = gen.save_snap_edgelist(tmp_path / "g.txt", n, src, dst,
                                  w if weighted else None)
    n2, src2, dst2, w2 = gen.load_snap_edgelist(path)
    assert n2 == n
    assert edge_weights(src2, dst2, w2) == edge_weights(src, dst, w)
    # both directions come back, in the generators' canonical order
    assert np.array_equal(src2, src) and np.array_equal(dst2, dst)
    assert np.array_equal(w2, w)


def test_snap_sparse_ids_compact_in_order(tmp_path):
    path = write(tmp_path, "10 500\n500 7\n7 10\n")
    n, src, dst, w = gen.load_snap_edgelist(path)
    # 7 -> 0, 10 -> 1, 500 -> 2
    assert n == 3
    assert edge_weights(src, dst, w) == {(0, 1): 1.0, (0, 2): 1.0,
                                         (1, 2): 1.0}


def test_snap_comment_lines_are_skipped(tmp_path):
    path = write(tmp_path, "# SNAP header\n% matrix-market style\n0 1\n"
                           "# a comment between rows\n1 2\n")
    n, src, dst, w = gen.load_snap_edgelist(path)
    assert n == 3
    assert set(edge_weights(src, dst, w)) == {(0, 1), (1, 2)}


def test_snap_four_columns_raise(tmp_path):
    path = write(tmp_path, "# header\n0 1 2 3\n")
    with pytest.raises(ValueError, match="2 or 3 columns"):
        gen.load_snap_edgelist(path)


SNAP_TEXT = "# tiny\n0 1\n1 2\n2 3\n3 0\n0 2\n"


@pytest.mark.parametrize("spec,direct", [
    ("er:200:2.5@7", lambda _: gen.er_graph(200, 2.5, seed=7)),
    ("rmat:8:4@3", lambda _: gen.rmat_graph(8, 4.0, seed=3)),
    ("pa:300:3@5", lambda _: gen.pa_graph(300, 3, seed=5)),
    ("grid:9@2", lambda _: gen.grid_graph(9, seed=2)),
    ("snap:{path}@4", lambda path: gen.load_snap_edgelist(path, seed=4)),
], ids=["er", "rmat", "pa", "grid", "snap"])
def test_graph_from_spec_equals_direct_call(tmp_path, spec, direct):
    path = write(tmp_path, SNAP_TEXT)
    got = gen.graph_from_spec(spec.format(path=path))
    want = direct(path)
    assert got[0] == want[0]
    for a, b in zip(got[1:], want[1:]):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_graph_from_spec_unknown_kind_raises():
    with pytest.raises(ValueError, match="unknown graph spec kind"):
        gen.graph_from_spec("torus:8@1")
