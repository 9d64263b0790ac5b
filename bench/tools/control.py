#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness check: the program
as committed and the control (``harness/control.py``: stage 2 capped at
``--rounds`` rounds), each on every seed, in one process.

  python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 \
      --rounds 1 [--control-seeds 1,2]

A build cell builds the seed's graph and compares the sample of queries
a run compares (``kinds/build.py`` ``count_wrong``). One JSON line per (path, seed) with the
numbers compared. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))
from harness.spec import load_module  # noqa: E402


def bench_run():
    """``bench/run.py`` as a module: the same start-up as a run."""
    return load_module(BENCH_DIR / "run.py", "perfbench_run")


def build_rows(ctx, seeds, control_seeds, rounds: int) -> list:
    from harness import control, graph as graph_mod, traffic
    from repro.core import IndexConfig, ISLabelIndex
    build = ctx.cell.kind()
    cfg = ctx.cell.config
    base = ctx.cell.generator().generate(cfg, int(cfg["graph_seed"]))
    rows = []
    for path, icfg, path_seeds in (
            ("program", cfg["index"], seeds),
            (f"rounds<={rounds}", control.capped_config(cfg["index"], rounds),
             control_seeds)):
        for seed in path_seeds:
            g = graph_mod.reorder(base, traffic.rng_for(seed))
            idx = ISLabelIndex.build(*g, IndexConfig(**icfg))
            rows.append({"path": path, "seed": seed,
                         "wrong_answers": build.count_wrong(idx, g, seed)})
            print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None, *, require_chip: bool = True, root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--control-seeds", default=None,
                    help="seeds of the control (default: --seeds)")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    control_seeds = ([int(x) for x in args.control_seeds.split(",")]
                     if args.control_seeds else seeds)
    ctx = bench_run().context(args.workload, seeds[0], 0.0,
                              require_chip=require_chip, root=root)
    build_rows(ctx, seeds, control_seeds, args.rounds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
