"""The run's report: earlier lines for what a reader of the run needs,
the numbers compared beside their limits as the last lines on standard
error, and the one JSON result line, last on standard output."""
from __future__ import annotations

import json
import math
import sys


def log(msg: str) -> None:
    print(msg, flush=True)


def metric_block(entries: list, values: dict) -> dict:
    """``{name: {"value", "unit"}}`` for each entry that has a value.
    A value of None (a reader that found nothing) is left out."""
    out = {}
    for m in entries:
        v = values.get(m["name"])
        if v is None:
            continue
        v = float(v)
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} is not finite: {v}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def emit(*, cell, trace: bool, values: dict, correct: bool, attempted: int,
         failed: int, device: dict, checks: dict,
         breakdown: dict | None = None) -> dict:
    """Print the result line. ``checks`` maps a short name to ``(value,
    limit)``: each number the correctness decision compared."""
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = metric_block(entries, values)
    if not trace:
        missing = [m["name"] for m in entries if m["name"] not in metrics]
        if missing:
            raise ValueError(f"end-to-end metrics without a value: {missing}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return line
