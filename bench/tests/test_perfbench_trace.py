"""The trace reduction: interval union, idle share, per-scope device
time and the breakdown, on synthetic intervals and on a trace recorded
on one TPU v5e.

``data/v5e_serve.xplane.pb`` is 80 ms of a profiled serving window of
the kron-g500-s14 index on one v5e (``DistanceServer.serve_trace``,
uniform pairs): the events of that interval kept, the rest dropped, and
a ``bench.window`` span over it."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_serve.xplane.pb"


def test_union_merges_overlaps_and_drops_empty():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 3.0), (4.0, 5.0), (4.5, 4.7)]
    assert trace.merge(iv) == [(0.0, 2.0), (4.0, 5.0)]
    assert trace.union_length(iv) == pytest.approx(3.0)
    assert trace.union_length([]) == 0.0


def _summary():
    ops = [trace.Op(1.0, 2.0, "fusion.1", "jit(run)/islabel.label_intersect/x"),
           trace.Op(2.0, 2.5, "fusion.2", "jit(run)/islabel.label_intersect/y"),
           trace.Op(3.0, 6.0, "while.3", "jit(run)/islabel.core_relax_ell/while"),
           trace.Op(4.0, 5.0, "fusion.4", "jit(run)/islabel.core_relax_ell/body")]
    host = [(6.0, 9.5, "_pump_loop"), (6.5, 7.0, "sleep"),
            (0.0, 1.0, "submit")]
    return trace.TraceSummary((0.0, 10.0), [ops], host)


def test_busy_idle_and_scope_time():
    s = _summary()
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(1.5 + 3.0)
    assert s.idle_pct() == pytest.approx(55.0)
    # nested ops count once: the union, not the sum
    assert s.scope_seconds("islabel.core_relax") == pytest.approx(3.0)
    assert s.scope_seconds("islabel.label_intersect") == pytest.approx(1.5)
    assert s.scope_seconds("islabel.core_relax_dense") == 0.0


def test_busy_is_averaged_over_devices():
    s = _summary()
    s.device_ops.append([trace.Op(0.0, 10.0, "x", "")])
    assert s.busy_s == pytest.approx((4.5 + 10.0) / 2)


def test_breakdown_top_ops_and_idle_gaps():
    b = _summary().breakdown()
    # own time, named with the scope: the loop's body op is not the loop's
    assert b["device_ops"] == [
        ["while.3 (jit(run)/islabel.core_relax_ell/while)", pytest.approx(2.0)],
        ["fusion.1 (jit(run)/islabel.label_intersect/x)", pytest.approx(1.0)],
        ["fusion.4 (jit(run)/islabel.core_relax_ell/body)", pytest.approx(1.0)],
        ["fusion.2 (jit(run)/islabel.label_intersect/y)", pytest.approx(0.5)]]
    gaps = b["idle_gaps"]
    # gaps: [0,1] 1.0, [2.5,3] 0.5, [6,10] 4.0, longest first
    assert [g[1] for g in gaps] == [pytest.approx(4.0), pytest.approx(1.0),
                                    pytest.approx(0.5)]
    # the shortest host event covering at least half of the gap
    assert gaps[0][0] == "_pump_loop"
    assert gaps[1][0] == "submit"
    assert gaps[2][0] == "idle"


def test_no_device_reads_nothing():
    s = trace.TraceSummary((0.0, 1.0), [], [])
    assert s.idle_pct() is None and s.busy_s == 0.0
    assert s.breakdown() == {"device_ops": [], "idle_gaps": []}


def test_recorded_chip_trace():
    s = trace.summarize(trace.load_xspace(RECORDED), "bench.window")
    assert s.window_s == pytest.approx(0.08)
    assert len(s.device_ops) == 1 and s.device_ops[0]
    assert 0.0 < s.busy_s <= s.window_s
    assert 0.0 <= s.idle_pct() < 100.0
    stage1 = s.scope_seconds("islabel.label_intersect")
    stage2 = s.scope_seconds("islabel.core_relax")
    assert 0.0 < stage1 and 0.0 < stage2
    assert stage1 + stage2 <= s.busy_s * (1 + 1e-9)
    # stage 2 (the ell_xla relaxation) is nearly all of the device time
    assert stage2 > 0.9 * s.busy_s
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert "islabel.core_relax_ell" in b["device_ops"][0][0]
    # own time: a loop op does not count its body's ops again
    assert sum(x[1] for x in b["device_ops"]) <= s.busy_s * (1 + 1e-9)
    assert any("_execute" in g[0] or "block_until_ready" in g[0]
               for g in b["idle_gaps"])


def test_self_time_subtracts_nested_ops():
    ops = [trace.Op(0.0, 10.0, "while", ""), trace.Op(1.0, 3.0, "a", ""),
           trace.Op(4.0, 5.0, "b", ""), trace.Op(4.2, 4.5, "c", ""),
           trace.Op(11.0, 12.0, "d", "")]
    assert trace.self_times(ops) == pytest.approx([7.0, 2.0, 0.7, 0.3, 1.0])
