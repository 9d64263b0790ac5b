"""Tier-1 tests for ``repro.obs``: the metric registry (bucket
boundaries, exact-numpy percentiles, labeled series, kind conflicts),
span tracing (nesting/ordering invariants, request coverage, Chrome
trace-event export), the compile-event watcher (region attribution and
the zero-recompile guarantee across version swaps), the bench-regression
gate, and the fault-metrics wiring into ``DistanceServer.stats()``.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import IndexConfig, ISLabelIndex
from repro.graphs import generators as gen
from repro.obs import (NULL_TRACER, REGISTRY, CompileWatcher, EventLog,
                       MetricRegistry, Tracer, compile_region,
                       write_chrome_trace, write_metrics)
from repro.obs.regression import (Regression, compare_dirs, compare_docs,
                                  extract_metrics)
from repro.serve import DistanceServer, make_trace
from repro.serve.metrics import KNOWN_LANES, ServeMetrics


# ----------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def index():
    """Small ER graph with 6 preallocated spare ids (mutation lane)."""
    n, src, dst, w = gen.er_graph(140, 2.4, seed=3)
    return ISLabelIndex.build(n + 6, src, dst, w,
                              IndexConfig(l_cap=128, label_chunk=64))


# ----------------------------------------------------------- registry
def test_counter_labeled_series_total_and_monotonic():
    reg = MetricRegistry()
    c = reg.counter("t.requests", "help text")
    c.inc(2, lane="mu")
    c.inc(3, lane="full")
    c.inc(1, lane="mu")
    assert c.value(lane="mu") == 3 and c.value(lane="full") == 3
    assert c.total() == 6
    # label order never creates a second series
    c.inc(1, lane="mu")
    assert c.value(lane="mu") == 4
    assert len(c.labels_seen()) == 2
    with pytest.raises(ValueError):
        c.inc(-1, lane="mu")


def test_gauge_set_and_inc():
    reg = MetricRegistry()
    g = reg.gauge("t.depth")
    g.set(5.0, q="a")
    g.inc(2.0, q="a")
    g.set(1.0, q="b")
    assert g.value(q="a") == 7.0 and g.value(q="b") == 1.0


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricRegistry()
    a = reg.counter("t.x")
    assert reg.counter("t.x") is a          # idempotent
    with pytest.raises(ValueError):
        reg.gauge("t.x")                    # same name, different kind
    with pytest.raises(ValueError):
        reg.histogram("t.x")


def test_registry_section_folds_labels():
    reg = MetricRegistry()
    reg.counter("f.events").inc(2, kind="rollback")
    reg.gauge("f.ema").set(0.5)
    reg.histogram("f.lat").observe(1.0)     # histograms excluded
    reg.counter("other.c").inc(1)           # prefix excluded
    sec = reg.section("f.")
    assert sec == {"f.events{kind=rollback}": 2.0, "f.ema": 0.5}


# ---------------------------------------------------------- histogram
def test_histogram_bucket_boundaries_are_inclusive_upper():
    reg = MetricRegistry()
    h = reg.histogram("t.h", buckets=(1.0, 2.0, 4.0), raw_cap=0)
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 5.0):
        h.observe(v)
    snap = h.snapshot()["series"][0]
    # v lands in the first bucket with v <= bound (searchsorted "left")
    assert snap["buckets"] == {"1.0": 2, "2.0": 2, "4.0": 1}
    assert snap["overflow"] == 1
    assert snap["count"] == 6 and snap["sum"] == pytest.approx(14.0)


def test_histogram_percentiles_match_numpy_exactly():
    reg = MetricRegistry()
    h = reg.histogram("t.lat", buckets=(0.25, 0.5, 1.0, 2.0))
    rng = np.random.default_rng(0)
    vals = rng.exponential(0.4, size=257)
    for v in vals:
        h.observe(v, server="s")
    for q in (0.0, 0.1, 0.5, 0.95, 0.99, 1.0):
        assert h.quantile(q, server="s") == pytest.approx(
            float(np.quantile(vals, q)), abs=0.0)
    assert h.mean(server="s") == pytest.approx(float(vals.mean()))
    assert h.max(server="s") == pytest.approx(float(vals.max()))
    assert h.count(server="s") == 257


def test_histogram_raw_overflow_falls_back_to_buckets():
    reg = MetricRegistry()
    h = reg.histogram("t.small", buckets=(1.0, 2.0, 8.0), raw_cap=8)
    vals = [0.5] * 6 + [1.5] * 6 + [3.0] * 4
    for v in vals:
        h.observe(v)
    assert h.values() == []                 # raw dropped past the cap
    assert h.count() == len(vals)
    # bucket interpolation stays inside the surrounding bucket bounds
    p50 = h.quantile(0.5)
    assert 1.0 <= p50 <= 2.0
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)
    assert h.max() == 8.0                   # top non-empty bucket bound


def test_histogram_rejects_bad_buckets():
    reg = MetricRegistry()
    with pytest.raises(ValueError):
        reg.histogram("t.b1", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("t.b2", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("t.b3", buckets=())


# -------------------------------------------------------------- spans
def test_span_nesting_ids_and_ordering_invariants():
    tr = Tracer("t")
    req = tr.start("request", 1.0, cat="request", trace_id=7)
    wait = tr.start("queue_wait", 1.0, cat="wait", parent=req)
    tr.end(wait, 1.5)
    ex = tr.add("device_exec", 1.5, 2.0, cat="exec", parent=req)
    tr.end(req, 2.0, lane="mu")
    assert [c.name for c in tr.children(req)] == ["queue_wait",
                                                  "device_exec"]
    assert wait.parent_id == req.span_id and ex.parent_id == req.span_id
    assert req.trace_id == 7 and req.duration == pytest.approx(1.0)
    assert req.args["lane"] == "mu"
    assert len({s.span_id for s in tr.spans}) == 3   # ids unique
    with pytest.raises(ValueError):
        tr.end(req, 3.0)                   # double end
    bad = tr.start("x", 5.0)
    with pytest.raises(ValueError):
        tr.end(bad, 4.0)                   # ends before it starts
    assert bad.open and bad not in tr.finished()


def test_request_coverage_math():
    tr = Tracer()
    full = tr.start("request", 0.0, cat="request")
    tr.add("queue_wait", 0.0, 0.75, parent=full)
    tr.add("device_exec", 0.75, 1.0, parent=full)
    tr.end(full, 1.0)
    half = tr.start("request", 2.0, cat="request")
    tr.add("queue_wait", 2.0, 2.5, parent=half)
    tr.end(half, 3.0)
    cov = tr.request_coverage()
    assert cov["requests"] == 2
    assert cov["min"] == pytest.approx(0.5)
    assert cov["mean"] == pytest.approx(0.75)


def test_chrome_export_is_well_formed():
    tr = Tracer("proc-name")
    s = tr.start("request", 0.010, cat="request", trace_id=3,
                 track="lane:mu")
    tr.add("device_exec", 0.010, 0.0115, parent=s, track="lane:mu")
    tr.end(s, 0.0115)
    tr.event("cache_hit", 0.02, cat="request", trace_id=4,
             track="lane:cache")
    doc = json.loads(json.dumps(tr.chrome()))   # JSON round-trip
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"X", "i", "M"}
    xs = [e for e in evs if e["ph"] == "X"]
    req = next(e for e in xs if e["name"] == "request")
    assert req["ts"] == pytest.approx(10_000.0)       # µs
    assert req["dur"] == pytest.approx(1_500.0)
    assert req["args"]["trace_id"] == 3
    child = next(e for e in xs if e["name"] == "device_exec")
    assert child["args"]["parent_id"] == req["args"]["span_id"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["name"]: e["args"]["name"] for e in meta
             if e["name"] == "process_name"}
    assert names["process_name"] == "proc-name"
    threads = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert {"lane:mu", "lane:cache"} <= threads
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["name"] == "cache_hit" and inst["s"] == "t"


def test_chrome_trace_file_roundtrip(tmp_path):
    tr = Tracer()
    tr.add("request", 0.0, 0.001, cat="request")
    p = write_chrome_trace(tmp_path / "sub" / "trace.json", tr)
    doc = json.loads(p.read_text())
    assert any(e.get("name") == "request" for e in doc["traceEvents"])


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    s = NULL_TRACER.start("x", 1.0)
    NULL_TRACER.end(s, 2.0)
    NULL_TRACER.add("y", 0.0, 1.0)
    NULL_TRACER.event("z", 0.0)
    assert NULL_TRACER.spans == [] and NULL_TRACER.events == []


# ----------------------------------------------------------- eventlog
def test_event_log_roundtrip_and_ring(tmp_path):
    path = tmp_path / "events.jsonl"
    with EventLog(path, keep=2) as log:
        log.log("start", ts=1.0, mode="mutate")
        log.log("swap", ts=2.0, vid=1)
        log.log("finish", ts=3.0)
        assert [e["kind"] for e in log.recent] == ["swap", "finish"]
    back = EventLog.read(path)
    assert [e["kind"] for e in back] == ["start", "swap", "finish"]
    assert [e["seq"] for e in back] == [0, 1, 2]
    assert back[0]["mode"] == "mutate" and back[1]["vid"] == 1


def test_write_metrics_snapshot(tmp_path):
    reg = MetricRegistry()
    reg.counter("w.c").inc(4, lane="mu")
    p = write_metrics(tmp_path / "m.json", reg, run="t")
    doc = json.loads(p.read_text())
    assert doc["run"] == "t"
    series = doc["metrics"]["w.c"]["series"]
    assert series == [{"labels": {"lane": "mu"}, "value": 4.0}]


# ------------------------------------------------------- serve metrics
def test_serve_metrics_lane_set_derives_from_observed_batches():
    m = ServeMetrics(server="lane-t")
    assert set(m.snapshot()["lanes"]) == set(KNOWN_LANES)  # idle default
    m.record_batch("mu", 8, 8, 1e-4, rounds=0)
    m.record_batch("aux", 16, 12, 2e-4, rounds=3)          # novel lane
    lanes = m.snapshot()["lanes"]
    assert set(lanes) == set(KNOWN_LANES) | {"aux"}
    assert lanes["aux"]["requests"] == 12
    assert lanes["aux"]["fill_ratio"] == pytest.approx(0.75)
    assert lanes["path"]["batches"] == 0                   # idle stays


def test_serve_metrics_instances_do_not_alias():
    a = ServeMetrics(server="same-name")
    b = ServeMetrics(server="same-name")   # same server label, new sid
    a.record_cache_hit()
    a.record_batch("mu", 8, 5, 1e-4, rounds=0)
    assert a.served == 6 and a.cache_hits == 1
    assert b.served == 0 and b.cache_hits == 0
    assert b.snapshot()["qps_compute"] == 0.0


# ----------------------------------------------------- regression gate
def _bench_doc(qps=1000.0, p99=2.0, hit=0.5, us=100.0, lane_mu=90):
    return {
        "rows": [{"name": "uniform-b32", "us_per_call": us},
                 {"name": "tiny", "us_per_call": 3.0}],   # under floor
        "results": [{
            "scenario": "uniform", "buckets": [32],
            "qps_compute": qps, "latency_ms": {"p99": p99},
            "cache_hit_rate": hit, "batch_fill_ratio": 0.8,
            "lanes": {"mu": {"requests": lane_mu},
                      "path": {"requests": 0}},            # idle: skipped
        }],
    }


def test_extract_metrics_kinds_and_floors():
    m = extract_metrics(_bench_doc())
    assert m["row:uniform-b32:us_per_call"].kind == "timing"
    assert "row:tiny:us_per_call" not in m        # noise floor
    assert m["cell:uniform-b32:qps_compute"].higher_better
    assert m["cell:uniform-b32:cache_hit_rate"].kind == "behavior"
    assert "cell:uniform-b32:lane_path_requests" not in m  # zero lane


def test_compare_docs_pass_fail_and_missing():
    base = _bench_doc()
    assert compare_docs("serving", base, _bench_doc()) == []
    regs = compare_docs("serving", base,
                        _bench_doc(qps=400.0, hit=0.2, us=300.0))
    names = {r.metric: r for r in regs}
    assert names["cell:uniform-b32:qps_compute"].kind == "timing"
    assert names["cell:uniform-b32:cache_hit_rate"].kind == "behavior"
    assert names["row:uniform-b32:us_per_call"].ratio == pytest.approx(3.0)
    # behavior drift beyond 5% trips even when timing tolerance is loose
    regs = compare_docs("serving", base, _bench_doc(hit=0.46),
                        timing_tolerance=10.0)
    assert [r.metric for r in regs] == ["cell:uniform-b32:cache_hit_rate"]
    # a metric that vanished from the fresh run is a regression
    fresh = _bench_doc()
    del fresh["results"][0]["cache_hit_rate"]
    regs = compare_docs("serving", base, fresh)
    assert [(r.metric, r.fresh) for r in regs] == \
        [("cell:uniform-b32:cache_hit_rate", None)]
    assert "missing" in regs[0].describe()


def test_compare_dirs_requires_named_tables(tmp_path):
    basedir, freshdir = tmp_path / "base", tmp_path / "fresh"
    basedir.mkdir(), freshdir.mkdir()
    (basedir / "BENCH_serving.json").write_text(json.dumps(_bench_doc()))
    # fresh run missing entirely: skipped without --tables...
    regs, compared, skipped = compare_dirs(basedir, freshdir)
    assert not regs and compared == [] and skipped == ["serving"]
    # ...but a required table missing is a coverage regression
    regs, _, _ = compare_dirs(basedir, freshdir, tables=["serving"])
    assert len(regs) == 1 and regs[0].kind == "coverage"
    (freshdir / "BENCH_serving.json").write_text(json.dumps(_bench_doc()))
    regs, compared, _ = compare_dirs(basedir, freshdir, tables=["serving"])
    assert not regs and compared == ["serving"]


# ------------------------------------------------------ compile watcher
def test_compile_watcher_attributes_regions():
    with CompileWatcher() as w:
        before = w.count("obs-test-zone")

        def f(x):
            return x * 2 + 1

        jf = jax.jit(f)
        with compile_region("obs-test-zone"):
            jf(jnp.arange(7)).block_until_ready()
        first = w.count("obs-test-zone") - before
        assert first >= 1                      # cold call compiled
        with compile_region("obs-test-zone"):
            jf(jnp.arange(7)).block_until_ready()
        assert w.count("obs-test-zone") - before == first  # cached: no new
    # stopped watcher is inert
    with compile_region("obs-test-zone"):
        jax.jit(lambda x: x - 3)(jnp.arange(5)).block_until_ready()
    assert w.count("obs-test-zone") - before == first


def test_zero_serve_read_compiles_across_version_swaps(index):
    """The exported zero-recompile guarantee: a readwrite replay with
    live version swaps never counts a backend compile in region
    ``serve_read`` (eager mutation scatters may compile — they land in
    region ``mutation``, never on the read path)."""
    with CompileWatcher() as w:
        read0 = w.count("serve_read")
        srv = DistanceServer(index, versioned=True, buckets=(8, 32),
                             max_wait_ms=1.0, cache_size=1024)
        srv.warmup()
        warm = w.count("warmup")
        nb = index.n - 6
        tr = make_trace("readwrite", n=index.n, num_requests=240,
                        rate_qps=5e4, seed=1, write_ratio=0.05,
                        n_read=nb, spares=range(nb, index.n),
                        attach_to=index.core_ids)
        ans, vids = srv.serve_readwrite_trace(tr)
        assert srv.metrics.mutations == tr.meta["writes"] > 0
        assert vids.max() == tr.meta["writes"]     # swaps really happened
        assert w.count("serve_read") - read0 == 0  # the guarantee
        assert warm > 0                            # warmup was attributed
        srv.drain()


# ------------------------------------------------- engine tracer wiring
def test_traced_serve_full_request_coverage(index, tmp_path):
    tracer = Tracer("test-serve")
    srv = DistanceServer(index, buckets=(8, 32), max_wait_ms=1.0,
                         cache_size=1024, tracer=tracer)
    tr = make_trace("repeated", n=index.n, num_requests=150, pool=40,
                    seed=2, rate_qps=2e4)
    got = srv.serve_trace(tr)
    want = np.asarray(index.query(np.asarray(tr.s), np.asarray(tr.t)),
                      np.float32)
    assert np.array_equal(got.astype(np.float32), want)
    snap = srv.stats()
    reqs = tracer.by_name("request")
    # every device-path request has a span; cache hits are instants
    assert len(reqs) == snap["served"] - snap["cache_hits"]
    hits = [e for e in tracer.events if e["name"] == "cache_hit"]
    assert len(hits) == snap["cache_hits"] > 0
    cov = tracer.request_coverage()
    assert cov["requests"] == len(reqs)
    assert cov["min"] >= 0.99                  # acceptance bound
    # span duration is exactly the recorded latency for that request
    by_rid = {s.trace_id: s for s in reqs}
    assert len(by_rid) == len(reqs)
    # the export opens: well-formed JSON with the expected tracks
    doc = json.loads(tracer.write_chrome(tmp_path / "t.json").read_text())
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e.get("name") == "thread_name"}
    assert any(t.startswith("lane:") for t in tracks)


# ------------------------------------------------- fault registry wiring
def test_fault_events_surface_in_registry_and_server_stats(index):
    from repro.serve.stragglers import HostTimingAggregator

    agg = HostTimingAggregator(threshold=1.3)
    for _ in range(4):
        # two fast hosts pin the fleet median at 1.0; h1 is persistently
        # 1.4x slower (below its own flag threshold, so the slowness
        # folds into its EMA rather than being discarded as a spike)
        agg.record("h0", 1.0), agg.record("h2", 1.0)
        agg.record("h1", 1.4)
    agg.record("h1", 10.0)                    # spike: flagged, not folded
    assert agg.stragglers() == ["h1"]
    assert REGISTRY.counter("fault.straggler_flags").value(host="h1") >= 1
    assert REGISTRY.gauge("fault.fleet_stragglers").value() == 1.0

    # ...and the serving stack surfaces the same section in stats()
    srv = DistanceServer(index, buckets=(8,), max_wait_ms=1.0)
    fault = srv.stats()["fault"]
    assert any(k.startswith("fault.step_seconds_ema") for k in fault)


# ---------------------------------------------- registry: new surfaces
def test_histogram_count_le_exact_then_bucketed():
    reg = MetricRegistry()
    h = reg.histogram("t.le", buckets=(1.0, 2.0, 8.0), raw_cap=8)
    for v in (0.5, 1.0, 1.5, 3.0):
        h.observe(v)
    # raw retained: exact at arbitrary bounds, boundary inclusive
    assert h.count_le(0.0) == 0
    assert h.count_le(1.0) == 2
    assert h.count_le(1.2) == 2
    assert h.count_le(100.0) == 4
    for v in [0.5] * 6:                       # push past raw_cap
        h.observe(v)
    assert h.values() == []
    # bucketed: cumulative count of buckets with bound <= the query
    # (an underestimate inside a bucket, never an overestimate)
    assert h.count_le(1.0) == 8
    assert h.count_le(1.9) == 8               # 1.5 now invisible
    assert h.count_le(2.0) == 9
    assert h.count_le(7.0) == 9


def test_registry_reset_detaches_old_metrics():
    reg = MetricRegistry()
    c = reg.counter("t.c", "")
    c.inc(5)
    reg.reset()
    assert reg.get("t.c") is None
    c2 = reg.counter("t.c", "")
    assert c2 is not c and c2.total() == 0
    c.inc(1)                                  # old handle records into a
    assert c2.total() == 0                    # detached object only


def test_registry_isolated_blocks_leaks_both_ways():
    reg = MetricRegistry()
    outer = reg.counter("t.out", "")
    outer.inc(3)
    with reg.isolated():
        assert reg.get("t.out") is None       # outside not visible
        reg.counter("t.in", "").inc(7)
        assert reg.get("t.in").total() == 7
    assert reg.get("t.in") is None            # inside did not leak
    assert reg.get("t.out").total() == 3      # restored intact


def test_render_prometheus_round_trip():
    from tests.test_frontend import parse_prometheus
    reg = MetricRegistry()
    reg.counter("serve.requests", "help with\nnewline").inc(
        3, server="a/r0", code="200")
    reg.gauge("obs.up", "").set(1.5)
    h = reg.histogram("serve.lat.seconds", "", buckets=(0.1, 1.0))
    h.observe(0.05, server='we"ird\\name')
    h.observe(0.5, server='we"ird\\name')
    h.observe(5.0, server='we"ird\\name')
    types, samples = parse_prometheus(reg.render_prometheus())
    # dotted names sanitize to underscores; kinds survive
    assert types == {"serve_requests": "counter", "obs_up": "gauge",
                     "serve_lat_seconds": "histogram"}
    assert samples[("serve_requests",
                    (("code", "200"), ("server", "a/r0")))] == 3.0
    assert samples[("obs_up", ())] == 1.5
    lbl = ("server", 'we"ird\\name')          # escapes round-trip
    assert samples[("serve_lat_seconds_bucket",
                    (("le", "0.1"), lbl))] == 1.0
    assert samples[("serve_lat_seconds_bucket",
                    (("le", "1.0"), lbl))] == 2.0
    assert samples[("serve_lat_seconds_bucket",
                    (("le", "+Inf"), lbl))] == 3.0
    assert samples[("serve_lat_seconds_count", (lbl,))] == 3.0
    assert samples[("serve_lat_seconds_sum",
                    (lbl,))] == pytest.approx(5.55)
    # prefix filter narrows the exposition
    assert "obs_up" not in reg.render_prometheus(prefix="serve.")
