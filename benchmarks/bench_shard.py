"""Sharded-query benchmark: shard counts × batch sizes through the
``repro.shard`` subsystem, each cell gated on bitwise exactness vs the
unsharded engine (full path *and* μ lane). Results accumulate in
``BENCH_shard.json``.

On real chips the suite runs in this one process, over the shard
counts the host's devices allow: a chip belongs to one process, so a
parent that has touched JAX would leave a child without it. On the CPU
(``JAX_PLATFORMS=cpu``) it simulates devices instead: ``XLA_FLAGS``
must be set before JAX initializes, so the suite re-execs itself with
``--xla_force_host_platform_device_count=<max shards>`` — deciding from
the environment alone, before this module touches JAX — and streams
the child's CSV rows through (the child writes the JSON).

  PYTHONPATH=src python -m benchmarks.bench_shard [--full] [--out DIR]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from benchmarks import common

SHARD_COUNTS = (1, 2, 4)


def _batch_sizes(full: bool):
    return (64, 256, 1024) if full else (64, 256)


def _reexec_with_devices(full: bool, n_dev: int) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_dev}"
                        ).strip()
    env["_BENCH_SHARD_CHILD"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.bench_shard",
           "--out", str(Path(common.OUT_DIR).resolve())] \
        + (["--full"] if full else [])
    r = subprocess.run(cmd, env=env, text=True, capture_output=True,
                       cwd=str(Path(__file__).resolve().parents[1]))
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(
            f"bench_shard subprocess failed:\n{r.stderr[-2000:]}")


def main(full: bool = False) -> None:
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and not os.environ.get("_BENCH_SHARD_CHILD")):
        _reexec_with_devices(full, max(SHARD_COUNTS))
        return
    _run(full)


def _run(full: bool) -> None:
    import jax
    from repro.core import ISLabelIndex, IndexConfig
    from repro.graphs import generators as gen
    from repro.shard import ShardedIndex

    if full:
        n, src, dst, w = gen.rmat_graph(14, avg_deg=6.0, seed=1)
        kind = "rmat14"
    else:
        n, src, dst, w = gen.er_graph(1 << 10, 2.2, seed=2)
        kind = "er10"
    idx = ISLabelIndex.build(n, src, dst, w, IndexConfig(l_cap=512))
    rng = np.random.default_rng(0)

    shard_counts = [p for p in SHARD_COUNTS if p <= len(jax.devices())]
    if os.environ.get("_BENCH_SHARD_CHILD"):
        assert shard_counts == list(SHARD_COUNTS), \
            "forced device count did not take effect in the subprocess"
    results, gate_passed = [], True
    for shards in shard_counts:
        sidx = ShardedIndex.from_index(idx, shards, strategy="level")
        for batch in _batch_sizes(full):
            s = rng.integers(0, n, batch).astype(np.int32)
            t = rng.integers(0, n, batch).astype(np.int32)
            base_fn = idx.engine.batch_fn()
            shard_fn = sidx.engine.batch_fn()
            # exactness gate: full path (ans + rounds) and the μ lane
            want_ans, want_rounds = base_fn(s, t)
            got_ans, got_rounds = shard_fn(s, t)
            exact = (np.array_equal(np.asarray(got_ans),
                                    np.asarray(want_ans))
                     and int(got_rounds) == int(want_rounds)
                     and np.array_equal(
                         np.asarray(sidx.engine.mu_batch_fn()(s, t)),
                         np.asarray(idx.engine.mu_batch_fn()(s, t))))
            gate_passed &= exact
            us_base, _ = common.timeit(base_fn, s, t)
            us_shard, _ = common.timeit(shard_fn, s, t)
            us_base *= 1e6
            us_shard *= 1e6
            collectives = sidx.engine.collective_count(batch)
            common.row("shard", f"p{shards}-q{batch}", us_shard,
                       base_us=round(us_base, 1),
                       rel=round(us_shard / us_base, 3) if us_base else 0.0,
                       collectives=collectives,
                       cap=sidx.engine.cap, exact=exact)
            results.append({
                "shards": shards, "batch": batch,
                "us_sharded": us_shard, "us_unsharded": us_base,
                "cap_per_shard": int(sidx.engine.cap),
                "entries_per_shard": sidx.shard_entry_counts().tolist(),
                "collectives_per_batch": collectives,
                "exact_vs_unsharded": bool(exact),
            })
    common.write_json("shard", {
        "graph": {"kind": kind, "n": int(n), "m": int(len(src))},
        "index": {"k": idx.k, "n_core": int(idx.stats.n_core),
                  "label_entries": int(idx.stats.label_entries),
                  "l_cap": int(idx.cfg.l_cap)},
        "devices": len(jax.devices()),
        "strategy": "level",
        "full": full,
        "gate": "bitwise vs QueryEngine.batch_fn/mu_batch_fn",
        "gate_passed": bool(gate_passed),
        "results": results,
    })
    # fail after writing, so a diverging sweep still records which
    # cells broke (exact_vs_unsharded=False) in BENCH_shard.json
    if not gate_passed:
        bad = [(r["shards"], r["batch"]) for r in results
               if not r["exact_vs_unsharded"]]
        raise AssertionError(f"sharded != unsharded for (P, Q) in {bad}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default=".")
    args = ap.parse_args()
    common.OUT_DIR = args.out
    main(full=args.full)
