"""Whole runs of the tiny build cell on the CPU with the timed path
broken underneath: each fault the cell can have comes out not correct."""
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))
import perfbench_tiny  # noqa: E402
from perfbench_tiny import run_cell  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return perfbench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def _initial_labels(hier, ids, d):
    """The labeling state before any level is labeled."""
    n, k = hier.n, hier.k
    ids0 = np.full(ids.shape, n, np.int32)
    d0 = np.full(d.shape, np.inf, np.float32)
    core = np.flatnonzero(hier.level == k)
    ids0[core, 0] = core
    d0[core, 0] = 0.0
    return ids0, d0


def _labels_unchanged(ids, d, hier):
    return _initial_labels(hier, ids, d)


def _half_labels_left_out(ids, d, hier):
    ids0, d0 = _initial_labels(hier, ids, d)
    ids, d = np.array(ids), np.array(d)
    half = hier.n // 2
    ids[half:], d[half:] = ids0[half:], d0[half:]
    return ids, d


def _label_altered(ids, d, hier):
    d = np.array(d)
    d[(d > 0) & np.isfinite(d)] += 1.0
    return np.asarray(ids), d


@pytest.mark.parametrize("fault", [_labels_unchanged, _half_labels_left_out,
                                   _label_altered])
def test_build_fault_is_not_correct(root, capsys, monkeypatch, fault):
    from repro.core import index
    build_labels = index.build_labels

    def broken(hier, cfg):
        ids, d, pred = build_labels(hier, cfg)
        ids, d = fault(np.asarray(ids), np.asarray(d), hier)
        return jnp.asarray(ids), jnp.asarray(d), pred
    monkeypatch.setattr(index, "build_labels", broken)
    line = run_cell(root, capsys, "tiny-kron.build")
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0
