"""Program spans in a reduced trace: the host events the program opens
with ``repro.obs.span`` (``islabel.build``, ``islabel.build.peel``, ...,
``islabel.sync``), matched by their bare name (the part before any
``#``, where the profiler writes a span's attributes), and the device
time and idle time under them.

A build's phases are separated by blocking reads, so a device op that
runs under a phase's span is that phase's work. Each function returns
None where the trace has no such span, or no device, to read."""
from __future__ import annotations

import bisect

from harness.trace import merge

BUILD = "islabel.build"
SYNC = "islabel.sync"


def bare(name: str) -> str:
    return name.split("#", 1)[0]


def intervals(summary, name: str) -> list:
    """``[(start, end), ...]`` of the host events named ``name``."""
    return [(s, e) for s, e, n in summary.host_events if bare(n) == name]


def builds(summary) -> int:
    """The builds in the trace: its ``islabel.build`` spans."""
    return len(intervals(summary, BUILD))


def covered(busy: list, start: float, end: float) -> float:
    """Length of ``[start, end]`` that the sorted, disjoint ``busy``
    intervals cover."""
    i = max(bisect.bisect_right(busy, (start,)) - 1, 0)
    total = 0.0
    while i < len(busy) and busy[i][0] < end:
        total += max(0.0, min(end, busy[i][1]) - max(start, busy[i][0]))
        i += 1
    return total


def _per_build(summary, name: str, idle: bool):
    spans = intervals(summary, name)
    n_builds = builds(summary)
    if not spans or not n_builds or not summary.device_ops:
        return None
    total = 0.0
    for ops in summary.device_ops:
        busy = merge([(o.start, o.end) for o in ops])
        for s, e in spans:
            on = covered(busy, s, e)
            total += (e - s - on) if idle else on
    return total / len(summary.device_ops) / n_builds


def device_seconds_per_build(summary, name: str):
    """Device-busy seconds under the ``name`` spans, per build: the union
    of device-op intervals inside them, averaged over the devices."""
    return _per_build(summary, name, idle=False)


def idle_seconds_per_build(summary, name: str):
    """Device-idle seconds under the ``name`` spans, per build."""
    return _per_build(summary, name, idle=True)


def stats_sum(stats: list, field: str):
    """Sum of a ``BuildStats`` field over the builds, None where a build
    does not have it (a program that does not count it)."""
    values = [getattr(s, field, None) for s in stats]
    if not values or any(v is None for v in values):
        return None
    return sum(values)


def fill_pct(stats: list, used: str, slots: str):
    """100 · Σ ``used`` / Σ ``slots`` over the builds."""
    u, c = stats_sum(stats, used), stats_sum(stats, slots)
    if u is None or not c:
        return None
    return 100.0 * u / c
