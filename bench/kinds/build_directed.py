"""Directed index build cell: whole ``DiISLabelIndex.build`` calls back
to back.

The graph is the configuration's arcs, the arc list in an order drawn
from ``--seed`` and every arc kept in its orientation (``reorder_arcs``;
``harness/graph.py``'s ``reorder`` flips pairs, which on a directed
graph would be another graph). Every seed builds the same sizes in
another order, so the work and the compiled programs are the same.
Set-up generates the graph and makes one build, which compiles on a
checkout's first run. The window then builds the same graph again and
again for ``--seconds``; each build is whole, so the window closes at
the end of the build that crosses ``--seconds``, and ``build_s`` is the
window over the builds in it.

Correctness: ``SAMPLE`` pairs drawn from the seed, sources among the
vertices with an out-arc and targets among those with an in-arc (as a
directed search benchmark draws its keys), are answered by the last
index built, through ``DiISLabelIndex.query``, and must equal the plain
reference (``harness/reference_directed.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from harness import result, traffic
from harness.device import memory_peak_bytes
from harness.reference_directed import DirectedReference
from harness.runctx import TRACE_S

SAMPLE = 256


def reorder_arcs(graph, rng):
    """The same directed graph with its arc list in a random order; no
    arc changes orientation."""
    n, src, dst, w = graph
    order = rng.permutation(len(src))
    return n, src[order], dst[order], w[order]


def sample_pairs(graph, seed: int):
    """``SAMPLE`` (s, t) pairs drawn from the seed: s has an out-arc, t
    an in-arc."""
    n, src, dst, _ = graph
    rng = traffic.rng_for(seed, 1)
    tails = np.flatnonzero(np.bincount(src, minlength=n)).astype(np.int32)
    heads = np.flatnonzero(np.bincount(dst, minlength=n)).astype(np.int32)
    return (tails[rng.integers(0, len(tails), SAMPLE)],
            heads[rng.integers(0, len(heads), SAMPLE)])


def count_wrong(idx, graph, seed: int) -> int:
    """Answers of ``idx`` to the seed's sample that differ from the plain
    reference over ``graph``."""
    import jax
    t = time.monotonic()
    s, d = sample_pairs(graph, seed)
    got = np.asarray(jax.device_get(idx.query(s, d)), np.float32)
    ref = DirectedReference(graph)
    wrong = sum(not ref.check(a, b, x) for a, b, x in zip(s, d, got))
    result.log(f"reference: {SAMPLE} pairs checked in "
               f"{time.monotonic() - t:.3f} s, "
               f"{int(np.isfinite(got).sum())} finite answers, {wrong} wrong")
    return wrong


def run(ctx) -> dict:
    from repro.core import IndexConfig
    from repro.core.directed import DiISLabelIndex
    from repro.obs import CompileWatcher

    if "stats" not in {f.name for f in dataclasses.fields(DiISLabelIndex)}:
        # fail at once where the program's directed build keeps no
        # BuildStats: the cell's metrics read them
        raise RuntimeError("DiISLabelIndex has no BuildStats: this "
                           "program cannot run a directed build cell")
    log = result.log
    cfg = ctx.cell.config
    base = ctx.cell.generator().generate(cfg, int(cfg["graph_seed"]))
    g = reorder_arcs(base, traffic.rng_for(ctx.seed))
    icfg = IndexConfig(**cfg["index"])
    t = time.monotonic()
    idx = DiISLabelIndex.build(*g, icfg)
    log(f"set-up build in {time.monotonic() - t:.3f} s: "
        f"{idx.stats.summary()}")
    setup_s = time.monotonic() - ctx.t_start

    # a traced run profiles the window's first builds, up to TRACE_S
    limit = min(TRACE_S, ctx.seconds) if ctx.trace else ctx.seconds
    stats = []
    with CompileWatcher() as watcher:
        c0 = watcher.count()
        with ctx.profile_span() if ctx.trace else contextlib.nullcontext():
            t_open = time.monotonic()
            while not stats or time.monotonic() - t_open < limit:
                idx = DiISLabelIndex.build(*g, icfg)
                stats.append(idx.stats)
            t_close = time.monotonic()
        compiles = watcher.count() - c0
    window_s = t_close - t_open
    dev = ctx.device_info()
    dev["memory_peak_bytes"] = memory_peak_bytes(ctx.devs)
    log(f"window {window_s:.6f} s: {len(stats)} builds")
    log(f"peel s per build: {[s.peel_seconds for s in stats]}")
    log(f"label s per build: {[s.label_seconds for s in stats]}")
    log(f"compiles in the window: {compiles}")
    log(f"peak HBM (memory_stats peak_bytes_in_use): "
        f"{dev['memory_peak_bytes']}")

    wrong = count_wrong(idx, g, ctx.seed)

    values = {"build_s": window_s / len(stats), "setup_s": setup_s}
    breakdown = None
    if ctx.trace:
        values, extra, breakdown = ctx.per_layer({"build_stats": stats})
        dev.update(extra)
    return result.emit(cell=ctx.cell, trace=ctx.trace, values=values,
                       correct=wrong == 0, attempted=len(stats), failed=0,
                       device=dev, checks={"wrong_answers": (wrong, 0)},
                       breakdown=breakdown)
