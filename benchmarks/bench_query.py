"""Paper Tables 4 + 5: query time over 1000 random queries, split into
Time(a) label fetch+intersection vs Time(b) core search, and broken down
by endpoint type (1: both core, 2: one core, 3: neither).

Each graph is measured through BOTH dispatch paths side by side:

  * ``reference`` — the jnp searchsorted merge + COO scatter relaxation,
    one dense [Q, n_core+1] frontier per direction for the whole batch.
  * ``kernel``    — the Pallas label-intersect kernel + the stage-2
    route ``CoreRelaxer.mode`` picks (fused/dense kernel or XLA round),
    query-chunked so the stage-2 frontier is [chunk, n_core+1] and the
    full batch never materializes a dense [Q, n_core+1] matrix in one
    launch. On TPU this is the compiled production path over the full
    batch; off-TPU it runs interpret mode (same program, jnp evaluation,
    ~1000x slower), so it is measured on a smaller query subset — the
    row is a correctness demonstration there, not a speed claim.

Every path's answers are checked *exactly* (integer edge weights, no
rounding slack) against the core/ref.py Dijkstra oracle before its row
is printed; a mismatch aborts the benchmark.

Two extra row families on the first graph gate this PR's optimizations:

  * ``relax_fused`` vs ``relax_loop`` — the same batch-64 query run
    with the stage-2 dispatcher pinned to the fused all-rounds kernel
    vs the XLA per-round gather loop; answers and round counts
    asserted bitwise-equal before the speedup is reported.
  * ``compressed`` — a ``label_dtype="auto"`` index (delta16 ids +
    int32 distances, decode fused into the kernels) Dijkstra-verified
    end to end, with the label-plane bytes saved.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import graphs_for_scale, row
from repro.core import ISLabelIndex, IndexConfig, ref
from repro.core.dispatch import CoreRelaxer
from repro.core.labels import encoded_nbytes


def _verify_exact(name, got, want):
    got = np.asarray(got)
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all(), f"{name}: reachability mismatch"
    if not np.array_equal(got[fin], want[fin].astype(np.float32)):
        bad = np.flatnonzero(got[fin] != want[fin].astype(np.float32))
        raise AssertionError(
            f"{name}: {len(bad)} answers differ from Dijkstra oracle")


def _fused_vs_loop(name, eng, kb, s, t, want):
    """Batch-64 query through the fused stage-2 kernel vs the XLA
    per-round loop (same engine, relaxer pinned per run): bitwise-equal
    answers and rounds asserted, speedup reported."""
    qf = 64
    sj, tj = jnp.asarray(s[:qf]), jnp.asarray(t[:qf])
    fused_rx = CoreRelaxer(eng.ce_src, eng.ce_dst, eng.ce_w, eng.n_core,
                           dense_threshold=2.0)
    if fused_rx.mode != "fused" and kb != "pallas":
        # interpret mode has no real VMEM; widen the budget so the
        # comparison still runs on wide-ELL graphs
        fused_rx = CoreRelaxer(eng.ce_src, eng.ce_dst, eng.ce_w,
                               eng.n_core, dense_threshold=2.0,
                               vmem_budget=1 << 62)
    if fused_rx.mode != "fused":
        # the core is above FUSED_MAX_V (or, compiled, its ELL too wide
        # for VMEM) — the XLA round IS the production route here, so
        # there is no fused row to measure.
        print(f"# {name}: core outside the fused kernel's range, "
              "skipping fused-vs-loop row")
        return
    loop_rx = CoreRelaxer(eng.ce_src, eng.ce_dst, eng.ce_w, eng.n_core,
                          fused=False, dense_threshold=2.0)
    assert loop_rx.mode == "ell_xla"
    orig = eng.relaxer
    out = {}
    try:
        for label, rx in (("relax_loop", loop_rx), ("relax_fused", fused_rx)):
            eng.relaxer = rx
            ans = eng.query(sj, tj, backend=kb, query_chunk=0)
            jax.block_until_ready(ans)             # compile + exactness run
            _verify_exact(f"{name}/{label}", ans, want[:qf])
            t0 = time.perf_counter()
            ans = eng.query(sj, tj, backend=kb, query_chunk=0)
            jax.block_until_ready(ans)
            out[label] = (time.perf_counter() - t0, np.asarray(ans),
                          eng._last_rounds)
    finally:
        eng.relaxer = orig
    tl, ans_l, r_l = out["relax_loop"]
    tf, ans_f, r_f = out["relax_fused"]
    assert r_f == r_l, f"{name}: fused/loop rounds differ ({r_f} != {r_l})"
    fin = np.isfinite(ans_l)
    assert (np.isfinite(ans_f) == fin).all() \
        and np.array_equal(ans_f[fin], ans_l[fin]), \
        f"{name}: fused/loop answers not bitwise-equal"
    row("table4_query", f"{name}/relax_loop", tl / qf * 1e6,
        backend=kb, batch=qf, relax_rounds=r_l, exact_vs_dijkstra=1)
    row("table4_query", f"{name}/relax_fused", tf / qf * 1e6,
        backend=kb, batch=qf, relax_rounds=r_f, exact_vs_dijkstra=1,
        bitwise_vs_loop=1, speedup_vs_loop=round(tl / tf, 2))


def _compressed_row(name, n, src, dst, w, backend, chunk, nq, s, t, want):
    """label_dtype="auto" index served end to end, Dijkstra-verified."""
    idx = ISLabelIndex.build(
        n, src, dst, w,
        IndexConfig(l_cap=1024, label_chunk=2048, label_dtype="auto"))
    eng = idx.engine
    sj, tj = jnp.asarray(s[:nq]), jnp.asarray(t[:nq])
    ans = eng.query(sj, tj, backend=backend, query_chunk=chunk)
    jax.block_until_ready(ans)
    _verify_exact(f"{name}/compressed", ans, want[:nq])
    t0 = time.perf_counter()
    ans = eng.query(sj, tj, backend=backend, query_chunk=chunk)
    jax.block_until_ready(ans)
    tot = time.perf_counter() - t0
    saved = 0.0
    if eng.codec != "none":
        nb_fp32 = np.asarray(eng.lbl_ids).nbytes + np.asarray(eng.lbl_d).nbytes
        nb_enc = encoded_nbytes(eng.enc_ids, eng.enc_base, eng.enc_d)
        saved = round(100.0 * (1 - nb_enc / nb_fp32), 1)
    row("table4_query", f"{name}/compressed", tot / nq * 1e6,
        backend=backend, query_chunk=chunk, n_queries=nq, codec=eng.codec,
        label_bytes_saved_pct=saved, exact_vs_dijkstra=1)


def main(full: bool = False):
    n_q = 1000
    on_tpu = jax.default_backend() == "tpu"
    # (row label, backend, query_chunk, n queries routed through the path)
    paths = [("reference", "reference", 0, n_q),
             ("kernel", "pallas", 256, n_q) if on_tpu else
             ("kernel", "interpret", 128, 256)]
    first = True
    for name, (n, src, dst, w) in graphs_for_scale(full):
        idx = ISLabelIndex.build(n, src, dst, w,
                                 IndexConfig(l_cap=1024, label_chunk=2048))
        r = np.random.default_rng(0)
        s = r.integers(0, n, n_q).astype(np.int32)
        t = r.integers(0, n, n_q).astype(np.int32)
        want = ref.dijkstra_oracle(n, src, dst, w, s)[np.arange(n_q), t]

        for label, backend, chunk, nq in paths:
            sj, tj = jnp.asarray(s[:nq]), jnp.asarray(t[:nq])
            # warmup (compile) — doubles as the exactness-gated run
            ans = idx.engine.query(sj, tj, backend=backend, query_chunk=chunk)
            jax.block_until_ready(ans)
            _verify_exact(f"{name}/{label}", ans, want[:nq])

            # Time (a): label gather + intersection only
            t0 = time.perf_counter()
            mu = idx.engine.query_mu_only(sj, tj, backend=backend)
            jax.block_until_ready(mu)
            ta = time.perf_counter() - t0

            # total
            t0 = time.perf_counter()
            ans = idx.engine.query(sj, tj, backend=backend, query_chunk=chunk)
            jax.block_until_ready(ans)
            tot = time.perf_counter() - t0
            tb = max(tot - ta, 0.0)
            row("table4_query", f"{name}/{label}", tot / nq * 1e6,
                backend=backend, query_chunk=chunk, n_queries=nq,
                total_ms=round(tot * 1e3, 2),
                time_a_ms=round(ta * 1e3, 2), time_b_ms=round(tb * 1e3, 2),
                relax_rounds=idx.engine._last_rounds, exact_vs_dijkstra=1)

        if first and idx.engine.n_core > 0:
            _, kb, chunk, nq = paths[-1]
            _fused_vs_loop(name, idx.engine, kb, s, t, want)
            _compressed_row(name, n, src, dst, w, kb, chunk,
                            min(nq, 256), s, t, want)
            first = False

        # Table 5: by endpoint type (default engine path)
        types = idx.query_types(s, t)
        for ty in (1, 2, 3):
            m = types == ty
            if m.sum() == 0:
                continue
            sq, tq = jnp.asarray(s[m]), jnp.asarray(t[m])
            jax.block_until_ready(idx.query(sq, tq))
            t0 = time.perf_counter()
            jax.block_until_ready(idx.query(sq, tq))
            dt = time.perf_counter() - t0
            row("table5_by_type", f"{name}/type{ty}",
                dt / max(int(m.sum()), 1) * 1e6, n_queries=int(m.sum()))


if __name__ == "__main__":
    main()
