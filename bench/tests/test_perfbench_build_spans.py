"""The build's span and counter readers: each on a synthetic trace with
known intervals, None where the program has no such span or counter,
and the counters in a traced run of the tiny build cell on the CPU."""
import json
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))
import perfbench_tiny  # noqa: E402
from harness import spans, trace  # noqa: E402
from harness.spec import load_module  # noqa: E402

METRICS = HERE.parent / "metrics"
TRACE_READERS = ["build.peel.device_s", "build.label.device_s",
                 "build.sync_idle_s"]
STATS_READERS = ["build.assemble_s", "build.label.slot_fill_pct",
                 "build.peel.aug_fill_pct"]


def reader(name):
    return load_module(METRICS / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def _summary(host=None, ops=None):
    """Two builds of 10 s each: peel [0,4] then label [4,9], assemble
    [9,10]; a blocking read at the end of each phase; the profiler
    writes attributes after a '#'."""
    if host is None:
        host = []
        for b in (0.0, 10.0):
            host += [(b, b + 10, "islabel.build"),
                     (b, b + 4, "islabel.build.peel"),
                     (b + 1, b + 2, "islabel.build.peel.level#level=1#"),
                     (b + 1.5, b + 2, "islabel.sync"),
                     (b + 4, b + 9, "islabel.build.label"),
                     (b + 8, b + 9, "islabel.build.label.check"),
                     (b + 8, b + 9, "islabel.sync"),
                     (b + 9, b + 10, "islabel.build.assemble")]
    if ops is None:
        # busy: peel [0.5,1.5] and [2,3.5] (nested op counted once);
        # label [4,8.5]; assemble [9.5,10]
        ops = []
        for b in (0.0, 10.0):
            ops += [trace.Op(b + 0.5, b + 1.5, "a", ""),
                    trace.Op(b + 2, b + 3.5, "while", ""),
                    trace.Op(b + 2.5, b + 3, "body", ""),
                    trace.Op(b + 4, b + 8.5, "gather", ""),
                    trace.Op(b + 9.5, b + 10, "copy", "")]
    return trace.TraceSummary((0.0, 20.0), [ops], host)


def _layer(summary=None, stats=()):
    return types.SimpleNamespace(trace=summary or _summary(),
                                 build_stats=list(stats), peak={})


def test_bare_names_and_builds():
    s = _summary()
    assert spans.bare("islabel.build.peel.level#level=1#") == \
        "islabel.build.peel.level"
    assert spans.builds(s) == 2
    # a child span is not its parent: names match whole
    assert spans.intervals(s, "islabel.build.peel") == [(0.0, 4.0),
                                                        (10.0, 14.0)]
    assert len(spans.intervals(s, "islabel.build.peel.level")) == 2


def test_phase_device_seconds_per_build():
    layer = _layer()
    assert reader("build.peel.device_s").read(layer) == pytest.approx(2.5)
    assert reader("build.label.device_s").read(layer) == pytest.approx(4.5)
    # the phases' device time is inside the busy time of the window
    busy_per_build = layer.trace.busy_s / 2
    assert 2.5 + 4.5 <= busy_per_build


def test_sync_idle_seconds_per_build():
    # idle under [1.5,2]: 0.5; under [8,9]: 0.5 (busy until 8.5)
    assert reader("build.sync_idle_s").read(_layer()) == pytest.approx(1.0)


def test_device_seconds_average_over_devices():
    s = _summary()
    s.device_ops.append([trace.Op(0.0, 20.0, "x", "")])
    assert spans.device_seconds_per_build(s, "islabel.build.peel") == \
        pytest.approx((2.5 + 4.0) / 2)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_none_without_spans_or_device(name):
    # a program without the spans (only the harness's own events)
    bare = _summary(host=[(0.0, 20.0, "_build_hierarchy_device")])
    assert reader(name).read(_layer(bare)) is None
    # builds but no device plane (a CPU trace)
    cpu = trace.TraceSummary((0.0, 20.0), [], _summary().host_events)
    assert reader(name).read(_layer(cpu)) is None


def _stats(**kw):
    return types.SimpleNamespace(**kw)


def test_stats_readers():
    stats = [_stats(assemble_seconds=0.25, label_candidates=10,
                    label_slots=1000, peel_aug_edges=30, peel_aug_slots=100),
             _stats(assemble_seconds=0.75, label_candidates=30,
                    label_slots=1000, peel_aug_edges=10, peel_aug_slots=300)]
    layer = _layer(stats=stats)
    assert reader("build.assemble_s").read(layer) == pytest.approx(0.5)
    assert reader("build.label.slot_fill_pct").read(layer) == \
        pytest.approx(2.0)
    assert reader("build.peel.aug_fill_pct").read(layer) == \
        pytest.approx(10.0)


@pytest.mark.parametrize("name", STATS_READERS)
def test_stats_readers_none_without_counters(name):
    # a program whose BuildStats has only the phase seconds
    old = [_stats(peel_seconds=1.0, label_seconds=1.5)]
    assert reader(name).read(_layer(stats=old)) is None
    assert reader(name).read(_layer(stats=[])) is None


def test_traced_tiny_build_reports_the_counters(tmp_path, capsys):
    """A traced run of the tiny build cell with the new metrics declared:
    the counters are read from the builds; a CPU trace has no device
    plane, so the device readers find nothing and are left out."""
    root = perfbench_tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in TRACE_READERS + STATS_READERS:
        bench["per_layer"].append(
            {"name": name, "unit": "%" if name.endswith("pct") else "s",
             "better": "lower", "source": "program_span", "layer": "build",
             "moves": "build_s", "workloads": perfbench_tiny.BUILD})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = perfbench_tiny.run_cell(root, capsys, "tiny-kron.build", trace=1)
    m = line["metrics"]
    assert line["correct"] is True
    assert set(STATS_READERS) <= set(m)
    assert not set(TRACE_READERS) & set(m)
    for name in ("build.label.slot_fill_pct", "build.peel.aug_fill_pct"):
        assert 0.0 < m[name]["value"] <= 100.0
    assert 0.0 < m["build.assemble_s"]["value"] < m["build.label_s"]["value"]
