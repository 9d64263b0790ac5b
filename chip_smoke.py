"""Drive the distance service's main path once on a TPU and check it.

  python chip_smoke.py              one chip: build -> DistanceServer ->
                                    HTTP front end on a 10^6-vertex ER
                                    index, then every stage-2 route and
                                    the compressed label codec on the
                                    quickstart R-MAT graph
  python chip_smoke.py --chips 4    four chips: the sharded index
                                    (ShardedIndex over 4 devices) against
                                    the unsharded engine on a 10^5-vertex
                                    ER index, nothing else

Everything runs in this one process (a chip belongs to one process).
Every answer is checked bitwise against the jnp reference backend on
the same chip, and a sample against the Dijkstra oracle; generator
weights are integral, so equality is exact. Only when every check
passes is the last line of standard output the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a
TPU, or outside a checkout of the repository, it exits nonzero and
prints no such line.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import time
from pathlib import Path

ER_SPEC = "er:1000000:2.2@1"       # the largest build in docs/CONSTRUCTION.md
ER_L_CAP = 64
# The sharded path: the same ER family at a tenth of the size. Every
# second of a four-chip run holds four chips; sharding changes where
# the label blocks live, not the answer, and this core (about 19k
# vertices) still takes the "ell_xla" route the 10^6 core takes.
SHARD_SPEC = "er:100000:2.2@1"
BUCKETS = (64, 256)
N_TRACE = 2048
N_HTTP = 32
N_ORACLE = 256
REF_CHUNK = 64                     # reference backend: [64, n_core+1] frontiers


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  check ok: {what}", flush=True)


def bitwise_equal(a, b) -> bool:
    import numpy as np
    a = np.ascontiguousarray(a, np.float32)
    b = np.ascontiguousarray(b, np.float32)
    return a.shape == b.shape and np.array_equal(a.view(np.uint32),
                                                 b.view(np.uint32))


def log(msg: str) -> None:
    print(msg, flush=True)


def reference_answers(engine, s, t):
    """Distances from the jnp reference backend, in fixed-size chunks
    (one compile; bounded [chunk, n_core+1] frontiers)."""
    import numpy as np
    return np.asarray(engine.query(s, t, backend="reference",
                                   query_chunk=REF_CHUNK))


def build(spec: str, l_cap: int):
    from repro.core import ISLabelIndex, IndexConfig
    from repro.graphs.generators import graph_from_spec
    t0 = time.perf_counter()
    n, src, dst, w = graph_from_spec(spec)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx = ISLabelIndex.build(n, src, dst, w, IndexConfig(l_cap=l_cap))
    build_s = time.perf_counter() - t0
    log(f"[{spec}] generated in {gen_s:.2f} s, built in {build_s:.2f} s")
    log(f"  {idx.stats.summary()}")
    return idx, (n, src, dst, w)


def serve_phase(spec: str, l_cap: int, *, backend_required: str | None,
                n_trace: int = N_TRACE, n_http: int = N_HTTP,
                n_oracle: int = N_ORACLE, seed: int = 0) -> None:
    """build -> IndexRegistry/DistanceServer (warmup, hotspot replay)
    -> ServiceFrontend over HTTP; answers checked against the reference
    backend and the Dijkstra oracle."""
    import numpy as np
    from repro.core import ref
    from repro.kernels.backend import resolve_backend
    from repro.obs import CompileWatcher
    from repro.serve import (HttpClient, IndexRegistry, ServiceFrontend,
                             make_trace)

    idx, (n, src, dst, w) = build(spec, l_cap)
    engine = idx.engine
    backend = resolve_backend(None)
    mode = engine.relaxer.mode if engine.relaxer is not None else "none"
    log(f"  resolved backend: {backend}; stage-2 route "
        f"(engine.relaxer.mode): {mode}")
    if backend_required is not None:
        check(backend == backend_required,
              f"auto backend resolves to {backend_required}")

    with CompileWatcher() as watcher:
        registry = IndexRegistry()
        t0 = time.perf_counter()
        server = registry.register("er", idx, buckets=BUCKETS)
        log(f"  registered with buckets {BUCKETS}: warmup "
            f"{time.perf_counter() - t0:.2f} s, compiles by region "
            f"{watcher.snapshot()}")
        shapes0 = server.compile_cache_sizes()
        warm_reads = watcher.count("serve_read")

        trace = make_trace("hotspot", n=n, num_requests=n_trace, seed=seed)
        t0 = time.perf_counter()
        served = server.serve_trace(trace)
        log(f"  served {n_trace} hotspot queries in "
            f"{time.perf_counter() - t0:.2f} s (one run, not a benchmark)")

        rng = np.random.default_rng(seed + 1)
        hs = rng.integers(0, n, n_http).astype(np.int32)
        ht = rng.integers(0, n, n_http).astype(np.int32)
        frontend = ServiceFrontend(registry)
        host, port = frontend.start_background()
        try:
            with HttpClient(host, port, graph="er") as client:
                t0 = time.perf_counter()
                over_http = np.asarray(
                    [client.query(int(a), int(b))[0] for a, b in zip(hs, ht)],
                    np.float32)
                log(f"  {n_http} HTTP requests answered in "
                    f"{time.perf_counter() - t0:.2f} s")
        finally:
            frontend.stop()
        read_compiles = watcher.count("serve_read") - warm_reads
        log(f"  compiles by region after serving: {watcher.snapshot()}")
    check(read_compiles == 0, "0 serve_read compiles after warmup")
    check(server.compile_cache_sizes() == shapes0,
          "compiled-shape counts unchanged across serving")

    # bitwise against the reference backend, on the same chip
    s_all = np.concatenate([trace.s, hs]).astype(np.int32)
    t_all = np.concatenate([trace.t, ht]).astype(np.int32)
    got_all = np.concatenate([served, over_http])
    pairs, inv = np.unique(np.stack([s_all, t_all], 1), axis=0,
                           return_inverse=True)
    t0 = time.perf_counter()
    want = reference_answers(engine, pairs[:, 0], pairs[:, 1])
    log(f"  reference backend: {len(pairs)} distinct pairs in "
        f"{time.perf_counter() - t0:.2f} s")
    check(bitwise_equal(got_all, want[inv.ravel()]),
          f"{n_trace} served + {n_http} HTTP answers bitwise-equal to "
          f"the reference backend")
    check(bool(np.isfinite(got_all).any()), "some answers are finite")

    # a sample against the Dijkstra oracle (integral weights: exact ==)
    pick = np.random.default_rng(seed + 2).choice(len(s_all), n_oracle,
                                                  replace=False)
    srcs, row = np.unique(s_all[pick], return_inverse=True)
    t0 = time.perf_counter()
    oracle = ref.dijkstra_oracle(n, src, dst, w, srcs)[row.ravel(),
                                                       t_all[pick]]
    log(f"  Dijkstra oracle from {len(srcs)} sources in "
        f"{time.perf_counter() - t0:.2f} s")
    check(bitwise_equal(got_all[pick], oracle.astype(np.float32)),
          f"{n_oracle} sampled answers equal the Dijkstra oracle")


def routes_phase(spec: str, l_cap: int, n_queries: int = 256,
                 seed: int = 0) -> None:
    """The quickstart R-MAT graph: its small core through every stage-2
    route (dense minplus, fused, XLA gather) and its labels through the
    compressed codec, each bitwise against the reference backend."""
    import numpy as np
    from repro.core import QueryEngine
    from repro.core.dispatch import CoreRelaxer

    idx, (n, _, _, _) = build(spec, l_cap)
    eng = idx.engine
    log(f"  default stage-2 route (engine.relaxer.mode): "
        f"{eng.relaxer.mode}; core density {eng.relaxer.density:.4f}")
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, n_queries).astype(np.int32)
    t = rng.integers(0, n, n_queries).astype(np.int32)
    want = reference_answers(eng, s, t)
    check(bool(np.isfinite(want).any()), "some reference answers finite")

    default = eng.relaxer
    pins = {"dense": dict(dense_threshold=0.0),
            "fused": dict(dense_threshold=2.0),
            "ell_xla": dict(dense_threshold=2.0, fused=False)}
    try:
        for route, kw in pins.items():
            eng.relaxer = CoreRelaxer(eng.ce_src, eng.ce_dst, eng.ce_w,
                                      eng.n_core, **kw)
            check(eng.relaxer.mode == route, f"core pinned to {route}")
            got = np.asarray(eng.query(s, t))
            check(bitwise_equal(got, want),
                  f"{route} route bitwise-equal to the reference backend")
    finally:
        eng.relaxer = default

    packed = QueryEngine(eng.lbl_ids, eng.lbl_d, eng.core_pos,
                         (eng.ce_src, eng.ce_dst, eng.ce_w), n, eng.n_core,
                         label_dtype="compressed")
    log(f"  compressed engine codec: {packed.codec}; stage-2 route "
        f"{packed.relaxer.mode}")
    check(bitwise_equal(np.asarray(packed.query(s, t)), want),
          "compressed-label engine bitwise-equal to the reference backend")


def sharded_phase(spec: str, l_cap: int, num_shards: int = 4,
                  n_trace: int = N_TRACE, seed: int = 0) -> None:
    """ShardedIndex over ``num_shards`` chips against the unsharded
    engine on the same graph and trace, bitwise."""
    import numpy as np
    from repro.serve import make_trace
    from repro.shard import ShardedIndex

    idx, (n, _, _, _) = build(spec, l_cap)
    t0 = time.perf_counter()
    sidx = ShardedIndex.from_index(idx, num_shards)
    log(f"  sharded over {num_shards} devices in "
        f"{time.perf_counter() - t0:.2f} s; entries per shard "
        f"{sidx.shard_entry_counts().tolist()}; stage-2 route "
        f"{sidx.engine.relaxer.mode}")
    trace = make_trace("hotspot", n=n, num_requests=n_trace, seed=seed)
    base_fn, shard_fn = idx.engine.batch_fn(), sidx.engine.batch_fn()
    for bucket in BUCKETS:
        outs = []
        for fn in (base_fn, shard_fn):
            np.asarray(fn(trace.s[:bucket], trace.t[:bucket])[0])  # compile
            t0 = time.perf_counter()
            ans = [np.asarray(fn(trace.s[i:i + bucket],
                                 trace.t[i:i + bucket])[0])
                   for i in range(0, n_trace, bucket)]
            log(f"  bucket {bucket}: {'sharded' if fn is shard_fn else 'unsharded'}"
                f" {n_trace} queries in {time.perf_counter() - t0:.2f} s")
            outs.append(np.concatenate(ans))
        check(bitwise_equal(outs[1], outs[0]),
              f"bucket {bucket}: {num_shards}-shard answers bitwise-equal "
              f"to the unsharded engine")
        check(bool(np.isfinite(outs[0]).any()), "some answers are finite")
    check(sidx.engine.collective_count(BUCKETS[0]) == 1,
          "one collective per sharded batch")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the service path on one chip; 4: only the "
                         "sharded path over four chips")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} jax={jax.__version__} libtpu={libtpu}")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {device['count']}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")

    t_start = time.perf_counter()
    try:
        if args.chips == 4:
            sharded_phase(SHARD_SPEC, ER_L_CAP)
        else:
            serve_phase(ER_SPEC, ER_L_CAP, backend_required="pallas")
            routes_phase("rmat:12:8@1", 512)
    except CheckFailed as e:
        print(f"chip_smoke: CHECK FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all checks passed in {time.perf_counter() - t_start:.2f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
