"""Benchmark driver: one module per paper table. Prints
``table,name,us_per_call,derived`` CSV rows and writes one
machine-readable ``BENCH_<table>.json`` per suite (``--out``, default
cwd) so the perf trajectory accumulates across PRs.

A suite that raises (including an exactness-gate AssertionError, e.g.
``bench_shard``'s bitwise gate or ``bench_path``'s path validation)
is reported as an ERROR row and the driver exits nonzero — CI's
``bench-smoke`` job relies on this to fail on any gate violation while
still uploading every ``BENCH_*.json`` produced. A suite that returns
without emitting a single row is treated the same way (EmptySuite):
a silently-empty ``BENCH_*.json`` would make the downstream
``bench-gate`` regression check vacuously green.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only tableX]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale graphs (slow)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default=".",
                    help="directory for BENCH_<table>.json files")
    args = ap.parse_args()

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    from benchmarks import (bench_baselines, bench_construction,
                            bench_k_sweep, bench_kernels, bench_mutation,
                            bench_path, bench_query, bench_serving,
                            bench_shard, common, roofline_report)
    suites = {
        "table3_construction": bench_construction.main,
        "table4_5_query": bench_query.main,
        "table6_k_sweep": bench_k_sweep.main,
        "table8_baselines": bench_baselines.main,
        "kernels": bench_kernels.main,
        "serving": bench_serving.main,
        "shard": bench_shard.main,
        "path": bench_path.main,
        "mutation": bench_mutation.main,
        "roofline": roofline_report.main,
    }
    common.OUT_DIR = args.out
    print("table,name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        before = len(common._ROWS)
        try:
            fn(full=args.full)
        except Exception as e:
            print(f"{name},ERROR,0,{type(e).__name__}:{e}")
            traceback.print_exc()
            failed.append(name)
            continue
        if len(common._ROWS) == before:
            print(f"{name},ERROR,0,EmptySuite:suite emitted zero rows")
            failed.append(name)
    for path in common.flush_rows(args.out):
        print(f"# wrote {path}")
    if failed:
        print(f"# FAILED suites: {','.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
