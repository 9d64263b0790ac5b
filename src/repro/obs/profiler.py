"""JAX-level observability: compile-event watching, device-memory /
live-buffer gauges, program spans on the profiler's clock (``span``),
and ``jax.profiler`` session wrapping.

Compile watching turns the serving stack's zero-recompile discipline
(docs/SERVING.md, docs/MUTATION.md) from a test-time assertion into an
exported counter: ``CompileWatcher`` registers a ``jax.monitoring``
duration listener and counts XLA backend compiles into
``obs.xla_compiles`` — labeled by *region*, because not every compile
is equal. The serving engine tags its execution windows with
``compile_region``:

  warmup       pre-warming the bucketed entry points (compiles expected)
  serve_read   the distance hot path          — MUST stay 0 after warmup
  serve_path   pre-warmed path tiers (+ the metered host fallback,
               which is documented to compile at unwarmed shapes)
  mutation     COW apply / state build (eager scatters may compile
               small executables; never on the read path)
  build        ``ISLabelIndex.build`` (its own watcher stores the
               build's compiles in ``BuildStats``)
  other        anything untagged

``launch/serve.py --mode mutate`` exits nonzero if ``serve_read``
compiles are ever counted after warmup.

The cache-size probes in ``DistanceServer.compile_cache_sizes()`` are
a second, independent gate.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

from repro.obs.registry import REGISTRY

__all__ = ["CompileWatcher", "compile_region", "current_region",
           "device_memory_gauges", "version_family_gauges",
           "profiler_session", "span"]

# Duration events jax._src.dispatch emits per XLA backend compile (the
# jaxpr-trace event fires on cache *misses* at the jit layer too, which
# is why backend_compile is the recompile signal).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_region = threading.local()


def current_region() -> str:
    return getattr(_region, "name", "other")


@contextlib.contextmanager
def compile_region(name: str):
    """Tag compiles triggered inside this block with ``name``."""
    prev = current_region()
    _region.name = name
    try:
        yield
    finally:
        _region.name = prev


class CompileWatcher:
    """Counts XLA backend compiles per region into the registry.

    Use as a context manager or ``start()``/``stop()``. Counters:
      obs.xla_compiles{region=...}          compile count
      obs.xla_compile_seconds{region=...}   summed compile wall time
    """

    def __init__(self, registry=None):
        self.registry = registry if registry is not None else REGISTRY
        self.compiles = self.registry.counter(
            "obs.xla_compiles", "XLA backend compiles by region")
        self.compile_seconds = self.registry.counter(
            "obs.xla_compile_seconds", "XLA backend compile wall time")
        self._active = False

    # ------------------------------------------------------- listener
    def _on_event(self, event: str, duration: float, **kw) -> None:
        if not self._active or event != BACKEND_COMPILE_EVENT:
            return
        region = current_region()
        self.compiles.inc(1, region=region)
        self.compile_seconds.inc(float(duration), region=region)

    def start(self) -> "CompileWatcher":
        if self._active:
            return self
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._active = True
        return self

    def stop(self) -> None:
        if not self._active:
            return
        self._active = False
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -------------------------------------------------------- queries
    def count(self, region: str | None = None) -> int:
        if region is not None:
            return int(self.compiles.value(region=region))
        return int(self.compiles.total())

    def snapshot(self) -> dict:
        return {dict(k)["region"]: int(s[0])
                for k, s in self.compiles._series.items()}


# ------------------------------------------------------------- memory
def device_memory_gauges(registry=None) -> dict:
    """Sample process-wide live-buffer and device-memory gauges.

      obs.live_buffers                live jax.Array count
      obs.live_buffer_bytes           their summed nbytes
      obs.device_bytes_in_use{device} allocator stats where the backend
                                      exposes them (TPU/GPU; CPU: absent)
    """
    reg = registry if registry is not None else REGISTRY
    arrs = jax.live_arrays()
    nbytes = sum(int(getattr(a, "nbytes", 0)) for a in arrs)
    reg.gauge("obs.live_buffers", "live jax.Array count").set(len(arrs))
    reg.gauge("obs.live_buffer_bytes", "live jax.Array bytes").set(nbytes)
    out = {"live_buffers": len(arrs), "live_buffer_bytes": nbytes}
    g = reg.gauge("obs.device_bytes_in_use", "allocator bytes in use")
    for dev in jax.devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            g.set(stats["bytes_in_use"], device=str(dev.id))
            out[f"device{dev.id}_bytes_in_use"] = int(stats["bytes_in_use"])
    return out


def version_family_gauges(manager, registry=None, server: str = "default"
                          ) -> dict:
    """Per-version-family device footprint (docs/MUTATION.md):

      versions.live{server}         live version count
      versions.state_bytes{server}  summed device bytes of live
                                    ``VersionState`` pytrees (COW-shared
                                    leaves counted once, by id)
      versions.current_vid{server}
    """
    reg = registry if registry is not None else REGISTRY
    seen: set = set()
    nbytes = 0
    for vid in manager.live_versions():
        state = manager._versions[vid].state
        if state is None:
            continue
        for leaf in jax.tree_util.tree_leaves(state):
            if id(leaf) not in seen:
                seen.add(id(leaf))
                nbytes += int(getattr(leaf, "nbytes", 0))
    live = len(manager.live_versions())
    reg.gauge("versions.live", "live index versions").set(live,
                                                          server=server)
    reg.gauge("versions.state_bytes",
              "device bytes pinned by live version states").set(
        nbytes, server=server)
    reg.gauge("versions.current_vid", "published version id").set(
        manager.current.vid, server=server)
    return {"live": live, "state_bytes": nbytes,
            "current_vid": manager.current.vid}


# ------------------------------------------------------------ profiler
@contextlib.contextmanager
def profiler_session(log_dir: str | None):
    """``jax.profiler.trace`` wrapper: a no-op when ``log_dir`` is falsy,
    so call sites need no branching; a profiler that fails to start
    raises. The written trace opens in TensorBoard / Perfetto and
    carries the ``jax.named_scope`` annotations the kernel dispatch
    layer emits (islabel.label_intersect / islabel.core_relax*)."""
    if not log_dir:
        yield False
        return
    with jax.profiler.trace(str(log_dir)):
        yield True


class span:
    """A named program span: a ``jax.profiler.TraceAnnotation`` (a host
    event in the same XSpace, on the same clock, as the device ops when
    a profiler runs; about a microsecond when none does) that also times
    itself on ``time.perf_counter``. Always on.

      with span("islabel.build.peel.level", level=i) as sp:
          ...
      sp.seconds          # wall time of the block
    """

    __slots__ = ("_ann", "_t0", "seconds")

    def __init__(self, name: str, **attrs):
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        return False
