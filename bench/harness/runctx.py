"""What one run knows: its cell, seed, window and device, where it may
write, and the pieces every cell kind uses to profile its window and
read its per-layer metrics."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import time
import types
from pathlib import Path

from harness import device, trace as trace_mod
from harness.spec import Cell

WINDOW_SPAN = "bench.window"
# A traced run profiles about this many seconds of its window (a cell
# kind may round up to a whole step): a trace of the whole window can be
# too large to keep and to read in a run (10 s of serving the Kronecker
# scale-14 index wrote 208 MB on one TPU v5e).
TRACE_S = 3.0


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devs: list
    root: Path
    cache_dir: Path
    t_start: float

    @property
    def config_file(self) -> Path:
        return self.cell.bench_dir / "configs" / f"{self.cell.config_name}.json"

    @property
    def src_root(self) -> Path:
        return self.root / "src" / "repro"

    def device_info(self) -> dict:
        return device.describe(self.devs)

    def peaks(self) -> dict:
        return device.peaks_for(self.devs[0].device_kind,
                                self.cell.bench_dir / "peaks.json")

    @property
    def trace_dir(self) -> Path:
        return self.cache_dir / "trace" / self.cell.name

    @contextlib.contextmanager
    def profile_span(self):
        """Profile the device for the block, inside the window span (the
        host event the trace reduction clips to)."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.trace_dir))
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    @staticmethod
    def sleep_until(instant: float) -> None:
        delay = instant - time.monotonic()
        if delay > 0:
            time.sleep(delay)

    def per_layer(self, layer: dict):
        """Reduce the profile and call each per-layer metric's reader.
        Returns (values by name, the device block's busy_s and window_s,
        the breakdown)."""
        summary = trace_mod.reduce_dir(self.trace_dir, WINDOW_SPAN)
        ns = types.SimpleNamespace(trace=summary, peak=self.peaks(), **layer)
        values = {}
        for m in self.cell.per_layer:
            values[m["name"]] = self.cell.metric_reader(m["name"]).read(ns)
        extra = {"busy_s": summary.busy_s, "window_s": summary.window_s}
        return values, extra, summary.breakdown()
