"""From a profiler trace to device busy time, per-scope device time and
the breakdown.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``, an
``XSpace`` protobuf. Device planes are named ``/device:TPU:<i>``; their
``XLA Ops`` line holds one event per device operation (a ``while`` op
contains the ops of its body), and each op's event metadata carries its
``jax.named_scope`` path in the ``tf_op`` stat. Host planes hold the
threads' events, the benchmark's window span among them. Every interval
is clipped to the window span. The protobuf module is the one installed
with TensorFlow's profiler protos, loaded by path so TensorFlow itself
is never imported.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
SCOPE_STAT = "tf_op"
TOP = 10


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    return sum(e - s for s, e in merge(intervals))


def merge(intervals) -> list:
    """Sorted, disjoint cover of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Op:
    start: float      # seconds, on the trace's clock
    end: float
    name: str
    scope: str


@dataclasses.dataclass
class TraceSummary:
    window: tuple                 # (start, end) seconds
    device_ops: list              # per device: [Op, ...] clipped to the window
    host_events: list             # [(start, end, name)] clipped to the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Union of device-op intervals, averaged over the devices."""
        if not self.device_ops:
            return 0.0
        return sum(union_length([(o.start, o.end) for o in ops])
                   for ops in self.device_ops) / len(self.device_ops)

    def idle_pct(self) -> float | None:
        if self.window_s <= 0 or not self.device_ops:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def scope_seconds(self, scope: str) -> float:
        """Device time of the ops whose scope path contains ``scope``:
        the union of their intervals, averaged over the devices."""
        if not self.device_ops:
            return 0.0
        return sum(union_length([(o.start, o.end) for o in ops
                                 if scope in o.scope])
                   for ops in self.device_ops) / len(self.device_ops)

    def top_ops(self, k: int = TOP) -> list:
        """``[[name, seconds], ...]``: the device ops that took most time
        of their own (a ``while`` op's body ops are not its own), by the
        names the trace gives them with their scope, summed over
        devices."""
        total: dict = {}
        for ops in self.device_ops:
            for o, own in zip(ops, self_times(ops)):
                key = f"{o.name} ({o.scope})" if o.scope else o.name
                total[key] = total.get(key, 0.0) + own
        return sorted(([n, s] for n, s in total.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = TOP) -> list:
        """``[[label, seconds], ...]``: the longest gaps in which no op ran
        on the first device, each labelled by the host event that
        overlaps it most (``idle`` where none does)."""
        if not self.device_ops:
            return []
        busy = merge([(o.start, o.end) for o in self.device_ops[0]])
        lo, hi = self.window
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        return [[self.host_label(s, e), e - s] for s, e in gaps]

    def host_label(self, start: float, end: float) -> str:
        """What the host was doing in ``[start, end]``: the shortest host
        event that covers at least half of it, else the one that covers
        most of it, else ``idle``."""
        best, label, most = None, "idle", 0.0
        for hs, he, name in self.host_events:
            ov = min(end, he) - max(start, hs)
            if ov <= 0:
                continue
            if ov >= 0.5 * (end - start) and (best is None
                                               or he - hs < best):
                best, label = he - hs, name
            elif best is None and ov > most:
                most, label = ov, name
        return label

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def self_times(ops: list) -> list:
    """Each op's duration less the ops nested in it (one device line's
    ops nest properly: a loop op spans its body's ops)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    own = [o.end - o.start for o in ops]
    stack: list = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return own


def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ModuleNotFoundError("the profiler's xplane protos are not "
                                  "installed (tensorflow/tsl/profiler)")
    path = (Path(list(spec.submodule_search_locations)[0])
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _stat_value(stat):
    for field in ("str_value", "int64_value", "uint64_value",
                  "double_value", "ref_value"):
        if stat.HasField(field):
            return getattr(stat, field)
    return None


def _events(plane):
    """``(start_s, end_s, name, scope, line_name)`` for each event of the
    plane, its name and scope from its event metadata."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    meta = {}
    for mid, md in plane.event_metadata.items():
        scope = ""
        for st in md.stats:
            if stat_names.get(st.metadata_id) == SCOPE_STAT:
                v = _stat_value(st)
                if isinstance(v, int) and st.HasField("ref_value"):
                    v = stat_names.get(v, "")
                scope = str(v or "")
        name = md.display_name or md.name.split(" = ")[0].lstrip("%")
        meta[mid] = (name, scope)
    for line in plane.lines:
        base = line.timestamp_ns * 1e-9
        for ev in line.events:
            name, scope = meta.get(ev.metadata_id, ("", ""))
            s = base + ev.offset_ps * 1e-12
            yield s, s + ev.duration_ps * 1e-12, name, scope, line.name


def summarize(xspace, window_span: str) -> TraceSummary:
    """Reduce an ``XSpace`` to the window span's device ops and host
    events."""
    window = None
    host, devices = [], []
    for plane in xspace.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append([Op(s, e, n, sc)
                            for s, e, n, sc, ln in _events(plane)
                            if ln == OPS_LINE])
        elif plane.name.startswith(HOST_PREFIX):
            for s, e, n, _, _ in _events(plane):
                if n == window_span:
                    window = (s, e)
                elif e > s:
                    host.append((s, e, n))
    if window is None:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = window
    devices = [[Op(max(o.start, lo), min(o.end, hi), o.name, o.scope)
                for o in ops if min(o.end, hi) > max(o.start, lo)]
               for ops in devices]
    host = [(max(s, lo), min(e, hi), n) for s, e, n in host
            if min(e, hi) > max(s, lo)]
    return TraceSummary(window, devices, host)


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xspace(path: Path):
    xspace = _xplane_pb2().XSpace()
    xspace.ParseFromString(Path(path).read_bytes())
    return xspace


def reduce_dir(trace_dir: Path, window_span: str) -> TraceSummary:
    return summarize(load_xspace(find_xplane(trace_dir)), window_span)
