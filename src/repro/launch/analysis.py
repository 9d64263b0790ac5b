"""Side-effect-free compile-artifact analysis (shared by dryrun/perf and
importable from tests WITHOUT touching jax device state).

v5e hardware model (per chip): 197 TF/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import re

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _tuple_shapes(type_str: str):
    """Parse all array types out of an HLO result type string."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        out.append(n * _DTYPE_BYTES[dt])
    return out


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in optimized HLO.

    The text is the *partitioned per-device* module, so sizes are
    per-device; multiply by device count for global traffic."""
    per_kind = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(
            r"(?:ROOT\s+)?%?([\w.\-]*)\s*=\s*(.*?)\s*"
            r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        kind = m.group(3)
        size = sum(_tuple_shapes(m.group(2)))
        per_kind[kind] = per_kind.get(kind, 0) + size
    per_kind["total"] = sum(per_kind.values())
    return per_kind
