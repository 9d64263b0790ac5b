"""The chip a run is on: the check that there is one, what JAX reports
about it, the peak of its memory, and its published peaks."""
from __future__ import annotations

import json
from pathlib import Path


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind: str, table: Path) -> dict:
    peaks = json.loads(Path(table).read_text())
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in {table}; add its "
                       f"published peaks with their source")
    return peaks[kind]
