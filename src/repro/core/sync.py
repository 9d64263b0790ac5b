"""Blocking device→host sync accounting for the construction path.

Every device→host read the builders perform goes through ``host_read``
so the per-level sync budget — the tentpole constraint of the
device-resident build (docs/CONSTRUCTION.md) — is *measured*, not
asserted: ``bench_construction`` snapshots the counter around a build
and gates ``syncs_per_level <= 1``. ``jax.device_get`` blocks until the
dependency cone of its operand has executed, so each call counted here
is one real host stall, and each runs inside an ``islabel.sync`` span:
in a profile, the device-idle time under it is the stall.
"""
from __future__ import annotations

import jax

from repro.obs.profiler import span

_COUNT = 0


def host_read(x):
    """Blocking device→host transfer, counted. Returns numpy."""
    global _COUNT
    _COUNT += 1
    with span("islabel.sync"):
        return jax.device_get(x)


def sync_count() -> int:
    return _COUNT


class sync_span:
    """Context manager reporting the syncs issued inside its scope."""

    def __enter__(self):
        self._start = _COUNT
        return self

    def __exit__(self, *exc):
        self.count = _COUNT - self._start
        return False

    @property
    def so_far(self) -> int:
        return _COUNT - self._start
