"""Tier-1 tests for ``repro.serve.stragglers``: straggler detection
(EMA verdicts, flag streaks, eviction, fleet median view).

Timings are scripted, so every assertion here is exact.
"""
from __future__ import annotations

import pytest

from repro.serve.stragglers import HostTimingAggregator, StragglerMonitor


# ----------------------------------------------------------- monitor
def test_monitor_first_step_seeds_ema_without_verdict():
    mon = StragglerMonitor()
    v = mon.record(0.25)
    assert mon.ema == 0.25
    assert v == {"straggler": False, "evict": False, "ratio": 1.0}


def test_monitor_flags_streak_then_evicts():
    mon = StragglerMonitor(alpha=0.2, threshold=1.5, evict_after=3)
    mon.record(1.0)                       # seed EMA
    verdicts = [mon.record(2.0) for _ in range(3)]
    assert [v["straggler"] for v in verdicts] == [True, True, True]
    assert [v["evict"] for v in verdicts] == [False, False, True]
    # straggler steps never fold into the EMA, so the ratio is stable
    assert mon.ema == 1.0
    assert all(v["ratio"] == 2.0 for v in verdicts)


def test_monitor_flag_streak_resets_on_recovery():
    mon = StragglerMonitor(alpha=0.5, threshold=1.5, evict_after=3)
    mon.record(1.0)
    mon.record(2.0), mon.record(2.0)      # two flags
    assert mon.flags == 2
    v = mon.record(1.0)                   # recovery step
    assert not v["straggler"] and mon.flags == 0
    assert mon.ema == pytest.approx(1.0)  # 0.5*1.0 + 0.5*1.0
    # the streak starts over: two more slow steps still don't evict
    assert not mon.record(2.0)["evict"] and not mon.record(2.0)["evict"]
    assert mon.record(2.0)["evict"]


def test_monitor_ema_update_is_exact_and_history_complete():
    mon = StragglerMonitor(alpha=0.25, threshold=10.0)
    times = [1.0, 2.0, 1.0, 4.0]
    for s in times:
        mon.record(s)
    ema = 1.0
    for s in times[1:]:
        ema = 0.75 * ema + 0.25 * s
    assert mon.ema == pytest.approx(ema)
    assert [h[0] for h in mon.history] == times


def test_monitor_scripted_timings_are_deterministic():
    script = [1.0, 1.1, 3.0, 0.9, 3.0, 3.0, 1.0]
    runs = []
    for _ in range(2):
        mon = StragglerMonitor(evict_after=2)
        runs.append([mon.record(s) for s in script])
    assert runs[0] == runs[1]


def test_aggregator_flags_host_above_fleet_median():
    agg = HostTimingAggregator(threshold=1.3)
    for _ in range(4):
        for h, s in [("h0", 1.0), ("h1", 1.0), ("h2", 1.0), ("h3", 2.0)]:
            agg.record(h, s)
    assert agg.stragglers() == ["h3"]


def test_aggregator_empty_and_uniform_fleets():
    agg = HostTimingAggregator()
    assert agg.stragglers() == []
    for h in ("a", "b"):
        agg.record(h, 1.0)
    assert agg.stragglers() == []


def test_straggler_monitor_flags_and_evicts():
    m = StragglerMonitor(evict_after=3)
    for _ in range(10):
        m.record(0.1)
    verdicts = [m.record(0.5) for _ in range(3)]
    assert verdicts[0]["straggler"]
    assert verdicts[-1]["evict"]


def test_host_aggregator_median():
    agg = HostTimingAggregator()
    for t in range(20):
        for h in ("h0", "h1", "h2", "h3"):
            agg.record(h, 0.1 if h != "h3" else 0.25)
    assert agg.stragglers() == ["h3"]
