#!/usr/bin/env python3
"""Run one benchmark cell once.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration, traffic mix, cell kind and
per-layer metric readers are found by name under ``bench/`` (see
``bench/harness/spec.py``). ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled window.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness decision
compared, beside its limit. Everything else is printed on earlier lines.
Without a TPU, or with fewer chips than the cell asks for, it exits 1
and prints no result line.

JAX's persistent compilation cache and profiler traces live under
``bench/.cache/`` in the checkout, so only a cell's first run there
compiles.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def context(workload: str, seed: int, seconds: float, trace: bool = False,
            *, require_chip: bool = True, root: Path | None = None,
            t_start: float | None = None):
    """The ``RunContext`` of one run of ``workload`` in the checkout at
    ``root`` (default: this file's). Raises ``NoChip`` without a TPU
    unless ``require_chip`` is False (tests only: it runs on whatever JAX
    has)."""
    root = BENCH_DIR.parent if root is None else Path(root)
    bench_dir = root / BENCH_DIR.name
    cache_dir = bench_dir / ".cache"
    fresh = "jax" not in sys.modules
    if fresh:
        # before JAX is imported: it reads the variable once, and the
        # program keeps its compilation cache where the variable points;
        # the TPU runtime's logs stay in the checkout too
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir / "jax")
        os.environ.setdefault("TPU_LOG_DIR", str(cache_dir / "tpu_logs"))
    for p in (str(root / "src"), str(BENCH_DIR)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import device, runctx, spec

    cell = spec.load_cell(workload, root=root, bench_dir=bench_dir)
    devs = device.require_tpu(cell.chips) if require_chip else None
    import jax
    if devs is None:
        devs = jax.devices()[:cell.chips]
    if fresh:
        # every program goes to the cache, so a cell's later runs compile
        # nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return runctx.RunContext(
        cell=cell, seed=seed, seconds=float(seconds), trace=bool(trace),
        devs=devs, root=root, cache_dir=cache_dir,
        t_start=T_START if t_start is None else t_start)


def main(argv=None, **kw) -> int:
    """One run; ``kw`` as for ``context``."""
    args = parse_args(argv)
    sys.path.insert(0, str(BENCH_DIR))
    from harness.device import NoChip
    try:
        ctx = context(args.workload, args.seed, args.seconds,
                      bool(args.trace), **kw)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    ctx.cell.kind().run(ctx)
    return 0


if __name__ == "__main__":
    sys.exit(main())
