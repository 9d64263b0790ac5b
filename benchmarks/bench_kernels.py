"""Kernel microbenchmarks: dispatch (Pallas kernel) vs jnp-reference
paths side by side at serving shapes, with parity asserted between them
and a roofline position (bytes/FLOPs model from
``benchmarks.roofline_report``) merged into every row.

On TPU the kernel rows measure compiled pallas_call; off-TPU they run
interpret mode (same program, jnp evaluation) so the comparison is about
correctness there, while the reference rows track what ``auto`` dispatch
actually serves on this container.

CI runs this standalone as the kernel-parity gate:

  PYTHONPATH=src python -m benchmarks.bench_kernels \
      --preset tiny --backend interpret --strict-roofline

Any backend-parity mismatch raises AssertionError (nonzero exit);
``--strict-roofline`` additionally fails if any emitted row lacks a
roofline model, so new kernel rows can't silently skip the accounting.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timeit
from benchmarks.roofline_report import roofline_fields
from repro.core.dispatch import (_core_relax_dense, _core_relax_ell,
                                 _core_relax_fused, CoreRelaxer, core_relax,
                                 ell_round)
from repro.core.labels import LabelRows, decode_ids, encode_labels, \
    encoded_nbytes
from repro.core.query import label_intersect_mu
from repro.kernels.backend import pallas_interpret, resolve_backend
from repro.kernels.label_intersect.ops import (label_intersect,
                                               label_intersect_rows)
from repro.kernels.minplus_matmul.ops import minplus_matmul
from repro.kernels.minplus_matmul.ref import minplus_matmul_ref
from repro.kernels.spmv_relax.ops import coo_to_ell
from repro.kernels.spmv_relax.ref import spmv_relax_ref

# q/l/n: label-intersect batch;  m: minplus GEMM edge;  v/qb: core-relax
# vertex count (n_core+1, kept a multiple of 128 so no lane padding) and
# stacked frontier rows;  dv/dq: the small dense-core route's shapes.
PRESETS = {
    "tiny": dict(q=128, l=64, n=1 << 16, m=128, v=1 << 10, qb=16,
                 dv=256, dq=16),
    "default": dict(q=512, l=64, n=1 << 16, m=256, v=1 << 12, qb=64,
                    dv=256, dq=16),
    "full": dict(q=4096, l=64, n=1 << 20, m=512, v=1 << 13, qb=256,
                 dv=512, dq=32),
}
MAXR = 64          # static round cap for the relax sections


def _bitwise(a, b, what: str):
    a, b = np.asarray(a), np.asarray(b)
    fin = np.isfinite(a)
    assert (np.isfinite(b) == fin).all() and np.array_equal(a[fin], b[fin]), \
        f"{what} parity failed"


def _core_graph(rng, v: int):
    """Degree-8-regular (in-degree) core graph on n_core = v-1 vertices:
    max in-degree 8 keeps the ELL width at exactly ELL_D_WIDTH=16, so
    the spmv/fused roofline models describe the real layout."""
    n_core = v - 1
    e = 8 * n_core
    dst = np.repeat(np.arange(n_core), 8)
    src = rng.integers(0, n_core, e)
    w = rng.integers(1, 5, e).astype(np.float32)
    return n_core, src, dst, w


def _seeds(rng, qh: int, v: int):
    s = np.full((qh, v), np.inf, np.float32)
    s[np.arange(qh), rng.integers(0, v, qh)] = 0.0
    return jnp.asarray(s)


def main(full: bool = False, preset: str | None = None,
         backend: str | None = None, strict_roofline: bool = False):
    p = PRESETS[preset or ("full" if full else "default")]
    r = np.random.default_rng(0)
    kernel_backend = backend or (
        "pallas" if jax.default_backend() == "tpu" else "interpret")
    interp = pallas_interpret(kernel_backend)
    print(f"# auto dispatch resolves to: {resolve_backend(None)}; "
          f"kernel rows use backend={kernel_backend}")

    unmodeled: list[str] = []

    def krow(name: str, us: float, **derived):
        fields = roofline_fields(name, us)
        if fields is None:
            unmodeled.append(name)
        else:
            derived = {**derived, **fields}
        row("kernels", name, us, **derived)

    # ---- label intersection at serving shape: engine / reference /
    # kernel. Ids must be unique per row (real label rows are): on
    # duplicates the searchsorted reference keeps only the first
    # occurrence while the equality-join kernel min-reduces over all,
    # so μ would differ.
    q, l, n = p["q"], p["l"], p["n"]

    def _rows():
        return np.sort(np.stack([r.choice(n, l, replace=False)
                                 for _ in range(q)]), 1).astype(np.int32)

    ids_s = _rows()
    ids_t = _rows()
    d_s = r.random((q, l)).astype(np.float32)
    d_t = r.random((q, l)).astype(np.float32)
    args = (jnp.asarray(ids_s), jnp.asarray(d_s),
            jnp.asarray(ids_t), jnp.asarray(d_t))
    f = jax.jit(lambda a, b, c, d: label_intersect_mu(a, b, c, d, n, l))
    us, _ = timeit(f, *args)
    krow(f"label_intersect_engine[{q}x{l}]", us / q * 1e6,
         total_ms=round(us * 1e3, 3))
    g = jax.jit(lambda a, b, c, d: label_intersect(a, b, c, d, n,
                                                   backend="reference"))
    us_ref, mu_ref = timeit(g, *args)
    krow(f"label_intersect_ref[{q}x{l}]", us_ref / q * 1e6)
    h = jax.jit(lambda a, b, c, d: label_intersect(a, b, c, d, n,
                                                   backend=kernel_backend))
    us_ker, mu_ker = timeit(h, *args)
    krow(f"label_intersect_kernel[{q}x{l}]", us_ker / q * 1e6,
         backend=kernel_backend,
         speedup_vs_ref=round(us_ref / us_ker, 2))
    _bitwise(mu_ref, mu_ker, "label_intersect dispatch")

    # ---- packed (delta16-compressed) label intersection: decode fused
    # into the join kernel. Rows are built delta-encodable by
    # construction (bounded gaps) with a tail of pad slots on half the
    # rows; integral distances exercise the int32 distance plane.
    step_hi = max(2, (n // 2) // l)
    pid = (r.integers(0, n // 4, (q, 1))
           + np.cumsum(r.integers(1, step_hi, (q, l)), axis=1)
           ).astype(np.int32)
    pd = r.integers(0, 100, (q, l)).astype(np.float32)
    pid[::2, l - 4:] = n                      # contiguous pad tail
    pd[::2, l - 4:] = np.inf
    pid_t = np.roll(pid, 1, axis=0)           # forces real intersections
    pd_t = np.roll(pd, 1, axis=0)
    enc_s = encode_labels(pid, pd, n)
    enc_t = encode_labels(pid_t, pd_t, n)
    rows_s = LabelRows(*(jnp.asarray(x) for x in enc_s))
    rows_t = LabelRows(*(jnp.asarray(x) for x in enc_t))
    plain = jax.jit(lambda a, b, c, d: label_intersect(
        a, b, c, d, n, backend=kernel_backend))
    us_plain, mu_plain = timeit(
        plain, jnp.asarray(pid), jnp.asarray(pd),
        jnp.asarray(pid_t), jnp.asarray(pd_t))
    packed = jax.jit(lambda a, b: label_intersect_rows(
        a, b, n, codec="delta16", backend=kernel_backend))
    us_pk, mu_pk = timeit(packed, rows_s, rows_t)
    nb_plain = pid.nbytes + pd.nbytes
    krow(f"label_intersect_packed[{q}x{l}]", us_pk / q * 1e6,
         backend=kernel_backend,
         speedup_vs_fp32=round(us_plain / us_pk, 2),
         bytes_saved_pct=round(
             100.0 * (1 - encoded_nbytes(*enc_s) / nb_plain), 1))
    _bitwise(mu_plain, mu_pk, "label_intersect packed-codec")
    _bitwise(pid, decode_ids(rows_s.ids, rows_s.base, n),
             "delta16 id roundtrip")

    # ---- minplus matmul (dense-core building block): ref vs kernel
    m = p["m"]
    a2 = (r.random((m, m)) * 9).astype(np.float32)
    b2 = (r.random((m, m)) * 9).astype(np.float32)
    f = jax.jit(minplus_matmul_ref)
    us_ref, mp_ref = timeit(f, jnp.asarray(a2), jnp.asarray(b2))
    krow(f"minplus_ref[{m}^3]", us_ref * 1e6)
    g = jax.jit(lambda x, y: minplus_matmul(x, y, backend=kernel_backend))
    us_ker, mp_ker = timeit(g, jnp.asarray(a2), jnp.asarray(b2))
    krow(f"minplus_kernel[{m}^3]", us_ker * 1e6, backend=kernel_backend,
         speedup_vs_ref=round(us_ref / us_ker, 2))
    np.testing.assert_allclose(np.asarray(mp_ref), np.asarray(mp_ker),
                               rtol=1e-6)

    # ---- one relaxation round at core-graph shape: ref vs the XLA
    # gather round (the "ell_xla" stage-2 route; no Pallas form of a
    # single round lowers, see docs/KERNELS.md)
    v, qb = p["v"], p["qb"]
    n_core, src, dst, w = _core_graph(r, v)
    e = len(src)
    ids, ws = coo_to_ell(v, src, dst, w, d_width=16)
    dist = np.full((qb, v), np.inf, np.float32)
    dist[np.arange(qb), r.integers(0, v, qb)] = 0.0
    f = jax.jit(spmv_relax_ref)
    us_ref, rx_ref = timeit(f, jnp.asarray(dist), ids, ws)
    krow(f"spmv_relax_ref[q{qb},v{v}]", us_ref * 1e6,
         edges_per_s=round(qb * e / us_ref / 1e6, 1))
    g = jax.jit(lambda d, i, w_: ell_round(d.T, i.T, w_.T).T)
    us_xla, rx_xla = timeit(g, jnp.asarray(dist), ids, ws)
    krow(f"spmv_relax_xla[q{qb},v{v}]", us_xla * 1e6, backend="xla",
         edges_per_s=round(qb * e / us_xla / 1e6, 1))
    _bitwise(rx_ref, rx_xla, "spmv_relax xla round")

    # ---- whole core search, fused kernel vs the XLA per-round loop:
    # the same graph relaxed to its fixed point. Distances, the μ
    # answer, and the round count must agree bitwise (max over
    # per-block in-kernel exits == loop rounds); both checked against
    # the COO reference.
    qh = qb // 2
    seed_s = _seeds(r, qh, v)
    seed_t = _seeds(r, qh, v)
    mu = jnp.full((qh,), jnp.inf, jnp.float32)

    def fused_call(a, b):
        return _core_relax_fused(a, b, ids, ws, mu, n_core, MAXR, interp, 8)

    def loop_call(a, b):
        return _core_relax_ell(a, b, ids, ws, mu, n_core, MAXR)

    us_fu, (ans_fu, ds_fu, dt_fu, r_fu) = timeit(fused_call, seed_s, seed_t)
    us_lp, (ans_lp, ds_lp, dt_lp, r_lp) = timeit(loop_call, seed_s, seed_t)
    rounds = int(r_fu)
    assert rounds == int(r_lp), \
        f"fused/loop round-count parity failed ({rounds} != {int(r_lp)})"
    for pair in ((ans_fu, ans_lp), (ds_fu, ds_lp), (dt_fu, dt_lp)):
        _bitwise(pair[1], pair[0], "fused core-relax")
    ans_ref, ds_ref, dt_ref, r_ref = core_relax(
        seed_s, seed_t, jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(w), mu, n_core, MAXR)
    assert rounds == int(r_ref), "fused/reference round-count parity failed"
    for kr, rr in ((ans_fu, ans_ref), (ds_fu, ds_ref), (dt_fu, dt_ref)):
        _bitwise(rr, kr, "fused-vs-reference core-relax")
    krow(f"relax_loop_xla[q{qb},v{v},r{rounds}]", us_lp * 1e6,
         backend="xla", rounds=rounds)
    krow(f"fused_relax_kernel[q{qb},v{v},r{rounds}]", us_fu * 1e6,
         backend=kernel_backend, rounds=rounds,
         speedup_vs_loop=round(us_lp / us_fu, 2))

    # ---- dense-core route: small dense core relaxed via the
    # minplus_matmul tropical GEMM, parity vs the fused route
    dv, dq = p["dv"], p["dq"]
    dn_core = dv - 1
    de = int(0.08 * dn_core * dn_core)
    dsrc = r.integers(0, dn_core, de)
    ddst = r.integers(0, dn_core, de)
    dw = r.integers(1, 5, de).astype(np.float32)
    relaxer = CoreRelaxer(dsrc, ddst, dw, dn_core)
    assert relaxer.mode == "dense", \
        f"dense-core dispatch expected 'dense', got {relaxer.mode!r}"
    adj = relaxer.dense_adj()
    vp = adj.shape[0]
    dqh = dq // 2
    dseed_s = _seeds(r, dqh, dn_core + 1)
    dseed_t = _seeds(r, dqh, dn_core + 1)
    dmu = jnp.full((dqh,), jnp.inf, jnp.float32)

    def dense_call(a, b):
        return _core_relax_dense(a, b, adj, dmu, dn_core, MAXR, interp, 8)

    us_de, (ans_de, ds_de, dt_de, r_de) = timeit(dense_call, dseed_s, dseed_t)
    fu2 = CoreRelaxer(dsrc, ddst, dw, dn_core, dense_threshold=2.0)
    assert fu2.mode == "fused", \
        f"dense-core fallback expected 'fused', got {fu2.mode!r}"
    ans_f2, ds_f2, dt_f2, r_f2 = fu2.run(dseed_s, dseed_t, dmu, MAXR,
                                         kernel_backend)
    assert int(r_de) == int(r_f2), "dense/fused round-count parity failed"
    for kr, rr in ((ans_de, ans_f2), (ds_de, ds_f2), (dt_de, dt_f2)):
        _bitwise(rr, kr, "dense-vs-fused core-relax")
    krow(f"dense_relax_kernel[q{dq},v{vp},r{int(r_de)}]", us_de * 1e6,
         backend=kernel_backend, rounds=int(r_de),
         density=round(relaxer.density, 3))

    if strict_roofline and unmodeled:
        raise RuntimeError(
            "kernel rows without a roofline model: " + ", ".join(unmodeled))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default=None)
    ap.add_argument("--backend", default=None,
                    choices=["pallas", "interpret", "reference"])
    ap.add_argument("--strict-roofline", action="store_true",
                    help="fail if any emitted row lacks a roofline model")
    ap.add_argument("--full", action="store_true")
    a = ap.parse_args()
    main(full=a.full, preset=a.preset, backend=a.backend,
         strict_roofline=a.strict_roofline)
