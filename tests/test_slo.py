"""Tier-1 tests for the SLO burn-rate engine (``repro.obs.slo``):
window-rate math on cumulative samples, the strictly-above fire rule
(a burn exactly at threshold is budget-neutral), the ``min_events``
thin-window guard, fire/resolve hysteresis, monotonic-clock
enforcement, the poll sources over the metric registry, alert-event
emission into the ``EventLog``, and the ``breach_summary`` digest CI
gates on.

Every test passes an explicit ``MetricRegistry`` so nothing touches
the process-wide ``REGISTRY``.
"""
from __future__ import annotations

import json

import pytest

from repro.obs import (EventLog, MetricRegistry, SLOEngine, SLOSpec,
                       compiles_source, counter_source,
                       default_serving_slos, latency_source)

# objective 0.75 -> budget exactly 0.25 in binary; a 50% bad rate burns
# at exactly 2.0, so threshold ties are representable without rounding
EXACT = dict(objective=0.75, fast_window_s=10.0, slow_window_s=40.0,
             fast_burn=2.0, slow_burn=0.5, resolve_hold_s=5.0)


def _engine(*specs, log=None):
    return SLOEngine(specs, log=log, registry=MetricRegistry())


# ------------------------------------------------------------ spec rules
def test_spec_validation_and_budget():
    with pytest.raises(ValueError, match="objective"):
        SLOSpec("x", objective=1.0)
    with pytest.raises(ValueError, match="objective"):
        SLOSpec("x", objective=0.0)
    with pytest.raises(ValueError, match="fast window"):
        SLOSpec("x", fast_window_s=60.0, slow_window_s=30.0)
    assert SLOSpec("x", objective=0.75).budget == 0.25
    with pytest.raises(ValueError, match="duplicate"):
        _engine(SLOSpec("a"), SLOSpec("a"))


# --------------------------------------------------------- window rates
def test_empty_window_never_fires():
    eng = _engine(SLOSpec("a", **EXACT))
    assert eng.evaluate(100.0) == []
    st = eng.states["a"]
    assert not st.firing and st.burn_fast == 0.0 and st.burn_slow == 0.0


def test_window_rate_is_delta_over_trailing_window():
    eng = _engine(SLOSpec("a", **EXACT))
    st = eng.states["a"]
    # cumulative samples: 10 good by t=0, then 10 bad by t=20
    eng.record("a", 0.0, good=10)
    eng.record("a", 20.0, bad=10)
    # fast window [10, 20] starts at the t=0 sample (newest <= cutoff):
    # delta is the 10 bad events -> rate 1.0
    rate_f, n_f = st.window_rate(20.0, 10.0)
    assert rate_f == 1.0 and n_f == 10
    # whole-run window sees 10 bad / 20 total
    rate_s, n_s = st.window_rate(20.0, 40.0)
    assert rate_s == 0.5 and n_s == 20


def test_min_events_guards_thin_windows():
    eng = _engine(SLOSpec("a", min_events=10, **EXACT))
    eng.record("a", 1.0, bad=5)              # 100% bad but only 5 events
    assert eng.evaluate(1.0) == []
    assert not eng.states["a"].firing
    eng.record("a", 2.0, bad=5)              # now 10 events in window
    events = eng.evaluate(2.0)
    assert [e["state"] for e in events] == ["fire"]


def test_burn_exactly_at_threshold_does_not_fire():
    eng = _engine(SLOSpec("a", **EXACT))
    # 2 bad / 4 total -> rate 0.5 -> burn exactly fast_burn == 2.0
    eng.record("a", 1.0, good=2, bad=2)
    assert eng.evaluate(1.0) == []
    st = eng.states["a"]
    assert st.burn_fast == 2.0 and not st.firing
    # one more bad tips strictly above: 3/5 -> burn 2.4
    eng.record("a", 2.0, bad=1)
    assert [e["state"] for e in eng.evaluate(2.0)] == ["fire"]
    assert st.firing and st.fires == 1


def test_both_windows_must_burn():
    # a long-clean history keeps the slow window quiet: no fire even
    # when the fast window saturates
    eng = _engine(SLOSpec("a", **EXACT))
    eng.record("a", 0.0, good=1000)
    eng.record("a", 35.0, bad=4)      # fast: 4/4 bad; slow: 4/1004
    assert eng.evaluate(35.0) == []
    st = eng.states["a"]
    assert st.burn_fast == 4.0 and st.burn_slow < 0.5 and not st.firing


def test_fire_resolve_hysteresis_holds_through_flap():
    eng = _engine(SLOSpec("a", **EXACT))
    eng.record("a", 1.0, bad=4)
    assert [e["state"] for e in eng.evaluate(1.0)] == ["fire"]
    st = eng.states["a"]
    # burn falls back under threshold as good traffic arrives, but the
    # alert holds until the condition has been false for resolve_hold_s
    # measured from the last evaluation where it held (t=1.0)
    eng.record("a", 2.0, good=100)
    assert eng.evaluate(2.0) == [] and st.firing
    assert eng.evaluate(5.9) == [] and st.firing      # hold not elapsed
    events = eng.evaluate(6.0)                        # 5s after t=1
    assert [e["state"] for e in events] == ["resolve"]
    assert not st.firing and st.resolves == 1
    # no duplicate fire/resolve events on further quiet evaluations
    assert eng.evaluate(8.0) == []


def test_refire_after_resolve_counts_again():
    eng = _engine(SLOSpec("a", **EXACT))
    eng.record("a", 1.0, bad=4)
    eng.evaluate(1.0)
    eng.record("a", 2.0, good=100)
    eng.evaluate(7.0)
    # fresh burst: everything in the fast window [t-10, t] is bad again
    eng.record("a", 30.0, bad=400)
    assert [e["state"] for e in eng.evaluate(30.0)] == ["fire"]
    st = eng.states["a"]
    assert st.fires == 2 and st.resolves == 1


def test_serving_clock_must_be_monotonic():
    eng = _engine(SLOSpec("a", **EXACT))
    eng.record("a", 10.0, good=1)
    with pytest.raises(ValueError, match="monotonic"):
        eng.record("a", 5.0, good=1)


def test_attach_unknown_slo_raises():
    eng = _engine(SLOSpec("a", **EXACT))
    with pytest.raises(KeyError, match="unknown SLO"):
        eng.attach("nope", lambda: (0, 0))


# -------------------------------------------------------------- sources
def test_counter_source_reads_good_bad_pair():
    reg = MetricRegistry()
    ok = reg.counter("t.ok", "")
    err = reg.counter("t.err", "")
    probe = counter_source("t.ok", "t.err", registry=reg)
    assert probe() == (0, 0)                 # metrics may not exist yet
    ok.inc(7)
    err.inc(3)
    assert probe() == (7, 10)


def test_latency_source_threshold_and_server_filter():
    reg = MetricRegistry()
    h = reg.histogram("serve.latency_seconds", "",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5):
        h.observe(v, server="a", sid="1")
    h.observe(5.0, server="b", sid="1")
    all_servers = latency_source(0.1, registry=reg)
    assert all_servers() == (2, 4)           # <= 0.1s over both servers
    only_a = latency_source(0.1, registry=reg, servers=["a"])
    assert only_a() == (2, 3)
    assert latency_source(0.1, registry=reg, metric="missing")() == (0, 0)


def test_compiles_source_counts_every_compile_as_bad():
    class FakeWatcher:
        def count(self, region):
            return {"serve_read": 2}.get(region, 0)

    assert compiles_source(FakeWatcher())() == (0, 2)
    assert compiles_source(FakeWatcher(), region="other")() == (0, 0)


def test_poll_path_fires_from_attached_source():
    eng = _engine(SLOSpec("a", min_events=1, **EXACT))
    bad = {"n": 0}
    eng.attach("a", lambda: (0, bad["n"]))
    assert eng.step(1.0) == []               # empty source: no events
    bad["n"] = 4
    events = eng.step(2.0)
    assert [e["state"] for e in events] == ["fire"]


# ----------------------------------------------------- events + digests
def test_alert_events_land_in_event_log_as_json_lines(tmp_path):
    log = EventLog()
    eng = _engine(SLOSpec("a", **EXACT), log=log)
    eng.record("a", 1.0, bad=4)
    (ev,) = eng.evaluate(1.0)
    assert ev["kind"] == "slo_alert" and ev["slo"] == "a"
    assert ev["state"] == "fire" and ev["burn_fast"] == 4.0
    assert ev["fast_burn_threshold"] == 2.0
    assert log.recent[-1] is ev
    # JSON-lines round trip (the SSE stream sends exactly these dicts)
    line = json.dumps(ev)
    assert json.loads(line) == ev


def test_burn_gauges_and_alert_counter_update():
    reg = MetricRegistry()
    eng = SLOEngine([SLOSpec("a", **EXACT)], registry=reg)
    eng.record("a", 1.0, bad=4)
    eng.evaluate(1.0)
    g = reg.get("slo.burn_rate")
    assert g.value(slo="a", window="fast") == 4.0
    assert reg.get("slo.firing").value(slo="a") == 1.0
    assert reg.get("slo.alerts").total() == 1


def test_breach_summary_digest():
    eng = _engine(SLOSpec("a", **EXACT), SLOSpec("b", **EXACT))
    eng.record("a", 1.0, bad=4)
    eng.evaluate(1.0)
    eng.record("a", 2.0, good=100)
    eng.evaluate(7.0)                        # resolved, but fired_ever
    out = eng.breach_summary()
    assert out["fired"] == ["a"] and out["firing"] == []
    assert out["slos"]["a"]["fires"] == 1
    assert out["slos"]["a"]["max_burn_fast"] == 4.0
    assert out["slos"]["b"] == {"fires": 0, "resolves": 0,
                                "max_burn_fast": 0.0, "max_burn_slow": 0.0}
    snap = eng.snapshot()
    assert snap["a"]["fires"] == 1 and not snap["a"]["firing"]


def test_default_serving_slos_cover_the_standing_objectives():
    specs = default_serving_slos(fast_window_s=1.0, slow_window_s=4.0)
    assert [s.name for s in specs] == ["availability", "latency",
                                      "exactness", "read_compiles"]
    eng = SLOEngine(specs, registry=MetricRegistry())

    class OneCompile:
        def count(self, region):
            return 1

    eng.attach("read_compiles", compiles_source(OneCompile()))
    # a single serve_read compile is an instant page (zero thresholds)
    events = eng.step(0.5)
    assert [(e["slo"], e["state"]) for e in events] == \
        [("read_compiles", "fire")]
