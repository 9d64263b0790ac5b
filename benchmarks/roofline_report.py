"""Roofline accounting for the serving kernels (ROADMAP "raw speed").

Attaches an analytic bytes/FLOPs model to every kernel-suite row, so
each optimization PR can state its roofline position — arithmetic
intensity plus achieved GB/s and GFLOP/s at the measured
``us_per_call`` — before/after. ``bench_kernels`` merges these fields
directly into its ``BENCH_kernels.json`` rows via ``roofline_fields``
(and asserts coverage under ``--strict-roofline``); this module's
``main`` additionally emits a standalone ``roofline`` table.

Reads the kernel rows from the current driver run when available
(``benchmarks.run`` executes the kernels suite first) and falls back to
a previously written ``BENCH_kernels.json`` under ``--out``/cwd, so
``--only roofline`` works against the last kernel run:

  PYTHONPATH=src python -m benchmarks.run --only kernels --out bench-out
  PYTHONPATH=src python -m benchmarks.run --only roofline --out bench-out

Traffic models (compulsory bytes, fp32/int32 = 4 B):

* ``label_intersect[q x l]`` (per query): two id rows + two distance
  rows stream in (``16·l`` B) and the l×l equality join does a compare
  + candidate min-add per pair (``2·l²`` flops) — intensity grows as
  ``l/8``, so serving-shape label widths sit near the knee.
* ``label_intersect_packed[q x l]`` (per query): compressed rows
  (core/labels.py delta16) stream int16 deltas + int32 distances + a
  base scalar per side (``2·(6l+4)`` B); decode is in-register, join
  flops unchanged — intensity ~2.6x the fp32 rows.
* ``spmv_relax[q, v]`` (per round): dense distance block read+written
  (``8·q·v``) over a shared ELL structure (``8·v·d``), relaxing
  ``2·q·v·d`` flops — intensity bounded by ``d/4``, memory-bound.
* ``fused_relax[q, v, r]`` (whole search): the dist block crosses HBM
  ONCE (``8·q·v + 8·v·d``) while all ``r`` rounds' flops
  (``2·q·v·d·r``) run out of VMEM — intensity scales with rounds,
  which is the point of the fusion. ``relax_loop[...]`` is the same
  search one round per loop step (the XLA ``"ell_xla"`` route): ``r×``
  the bytes at equal flops.
* ``minplus[m^3]``: dense tropical GEMM, ``4·3·m²`` B compulsory,
  ``2·m³`` flops. ``dense_relax[q, v, r]``: r tropical GEMM rounds of
  the [q, v]×[v, v] frontier product (q = both frontiers stacked).
"""
from __future__ import annotations

import json
import re
from pathlib import Path

from benchmarks import common
from benchmarks.common import row

ELL_D_WIDTH = 16        # matches bench_kernels.py's coo_to_ell(d_width=16)


def label_intersect_model(q: int, l: int) -> tuple[float, float]:
    """(bytes, flops) per *query* — these rows report µs per query."""
    return 16.0 * l, 2.0 * l * l


def label_intersect_packed_model(q: int, l: int) -> tuple[float, float]:
    """Compressed rows per query: int16 delta (2l) + d plane (4l) +
    int32 base (4) per side; decode cumsum + join."""
    return 2.0 * (6.0 * l + 4.0), 2.0 * l * l + 4.0 * l


def spmv_relax_model(q: int, v: int,
                     d_width: int = ELL_D_WIDTH) -> tuple[float, float]:
    """(bytes, flops) for ONE relaxation round over the whole batch."""
    return 8.0 * q * v + 8.0 * v * d_width, 2.0 * q * v * d_width


def fused_relax_model(q: int, v: int, rounds: int,
                      d_width: int = ELL_D_WIDTH) -> tuple[float, float]:
    """Whole fused search: one HBM pass of dist + ELL, r rounds of
    flops in VMEM."""
    b, f = spmv_relax_model(q, v, d_width)
    return b, f * max(rounds, 1)


def relax_loop_model(q: int, v: int, rounds: int,
                     d_width: int = ELL_D_WIDTH) -> tuple[float, float]:
    """The same search one round per loop step: r× the HBM traffic."""
    b, f = spmv_relax_model(q, v, d_width)
    r = max(rounds, 1)
    return b * r, f * r


def minplus_model(m: int) -> tuple[float, float]:
    return 4.0 * 3.0 * m * m, 2.0 * m ** 3


def dense_relax_model(q: int, v: int, rounds: int) -> tuple[float, float]:
    """r rounds of the [q, v] × [v, v] tropical frontier GEMM (q = both
    query frontiers stacked, matching the relax row names)."""
    r = max(rounds, 1)
    return (4.0 * (q * v + v * v + q * v) * r,
            2.0 * q * v * v * r)


# name-pattern -> (bytes, flops); first match wins, so more specific
# patterns (packed, fused) come before their prefixes
MODELS = [
    (re.compile(r"label_intersect_packed\w*\[(\d+)x(\d+)\]"),
     lambda m: label_intersect_packed_model(int(m[1]), int(m[2]))),
    (re.compile(r"label_intersect_\w+\[(\d+)x(\d+)\]"),
     lambda m: label_intersect_model(int(m[1]), int(m[2]))),
    (re.compile(r"fused_relax\w*\[q(\d+),v(\d+),r(\d+)\]"),
     lambda m: fused_relax_model(int(m[1]), int(m[2]), int(m[3]))),
    (re.compile(r"relax_loop\w*\[q(\d+),v(\d+),r(\d+)\]"),
     lambda m: relax_loop_model(int(m[1]), int(m[2]), int(m[3]))),
    (re.compile(r"dense_relax\w*\[q(\d+),v(\d+),r(\d+)\]"),
     lambda m: dense_relax_model(int(m[1]), int(m[2]), int(m[3]))),
    (re.compile(r"spmv_relax_\w+\[q(\d+),v(\d+)\]"),
     lambda m: spmv_relax_model(int(m[1]), int(m[2]))),
    (re.compile(r"minplus_\w+\[(\d+)\^3\]"),
     lambda m: minplus_model(int(m[1]))),
]


def roofline_fields(name: str, us: float) -> dict | None:
    """Roofline-derived fields for a kernel row, or None when no model
    matches the row name. ``bench_kernels`` merges this into every row
    it emits (bytes/flops per call, intensity, achieved GB/s, GFLOP/s)."""
    for pat, model in MODELS:
        m = pat.match(name)
        if m:
            nbytes, flops = model(m)
            s = max(us, 1e-3) * 1e-6
            return {
                "bytes_per_call": nbytes,
                "flops_per_call": flops,
                "intensity": round(flops / nbytes, 3),
                "gbytes_per_s": round(nbytes / s / 1e9, 3),
                "gflops_per_s": round(flops / s / 1e9, 3),
            }
    return None


def _kernel_rows(out_dir: str) -> list[dict]:
    rows = [r for r in common._ROWS if r["table"] == "kernels"]
    if rows:
        return rows
    for base in (out_dir, "."):
        path = Path(base) / "BENCH_kernels.json"
        if path.exists():
            return json.loads(path.read_text()).get("rows", [])
    return []


def main(full: bool = False):
    rows = _kernel_rows(common.OUT_DIR)
    if not rows:
        print("# roofline: no kernel rows — run the kernels suite first "
              "(python -m benchmarks.run --only kernels, same --out)")
        return
    for r in rows:
        fields = roofline_fields(r["name"], r["us_per_call"])
        if fields is not None:
            row("roofline", r["name"], r["us_per_call"], **fields)


if __name__ == "__main__":
    main()
