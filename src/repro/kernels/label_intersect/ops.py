"""Backend-aware wrapper with shape padding for the label-intersect
kernel. ``backend`` selects pallas / interpret / jnp-reference (see
``repro.kernels.backend``); the legacy ``interpret=`` kwarg still forces
the pallas program when given explicitly."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.labels import PAD_D, PAD_DELTA, LabelRows, decode_rows
from repro.kernels.backend import pallas_interpret, resolve_backend
from repro.kernels.label_intersect.kernel import (
    LANES, label_intersect_kernel, label_intersect_packed_kernel)
from repro.kernels.label_intersect.ref import label_intersect_ref


def label_intersect(ids_s, d_s, ids_t, d_t, n_sentinel: int, *,
                    bq=8, backend=None, interpret=None):
    backend = resolve_backend(backend, interpret)
    if backend == "reference":
        return label_intersect_ref(ids_s.astype(jnp.int32),
                                   d_s.astype(jnp.float32),
                                   ids_t.astype(jnp.int32),
                                   d_t.astype(jnp.float32), n_sentinel)
    q, l = ids_s.shape
    qp = -(-q // bq) * bq
    lp = -(-l // LANES) * LANES

    def padi(x):
        return jnp.pad(x, ((0, qp - q), (0, lp - l)),
                       constant_values=n_sentinel)

    def padd(x):
        return jnp.pad(x, ((0, qp - q), (0, lp - l)), constant_values=jnp.inf)

    mu = label_intersect_kernel(
        padi(ids_s.astype(jnp.int32)), padd(d_s.astype(jnp.float32)),
        padi(ids_t.astype(jnp.int32)), padd(d_t.astype(jnp.float32)),
        n_sentinel=n_sentinel, bq=bq, interpret=pallas_interpret(backend))
    return mu[:q, 0]


def label_intersect_rows(rows_s: LabelRows, rows_t: LabelRows,
                         n_sentinel: int, *, codec: str = "none",
                         bq=8, backend=None):
    """μ over gathered ``LabelRows`` in either codec.

    codec "none" routes to the plain wrapper; "delta16" pads the
    compressed planes (delta pad = -1 marker, so padded slots decode to
    the sentinel) and runs the fused decode+join kernel — the reference
    backend decodes with jnp and reuses the searchsorted merge."""
    if codec == "none":
        return label_intersect(rows_s.ids, rows_s.d, rows_t.ids, rows_t.d,
                               n_sentinel, bq=bq, backend=backend)
    backend = resolve_backend(backend)
    if backend == "reference":
        ids_s, d_s = decode_rows(rows_s, n_sentinel, codec)
        ids_t, d_t = decode_rows(rows_t, n_sentinel, codec)
        return label_intersect_ref(ids_s, d_s, ids_t, d_t, n_sentinel)
    bq = max(bq, 16)                 # int16 planes tile at (16, 128)
    q, l = rows_s.ids.shape
    qp = -(-q // bq) * bq
    lp = -(-l // LANES) * LANES

    def pad_delta(x):
        return jnp.pad(x, ((0, qp - q), (0, lp - l)),
                       constant_values=PAD_DELTA)

    def pad_d(x):
        fill = jnp.inf if x.dtype == jnp.float32 else PAD_D
        return jnp.pad(x, ((0, qp - q), (0, lp - l)), constant_values=fill)

    def pad_base(x):                 # [Q] -> [Qp, 1]: a 2-D kernel block
        return jnp.pad(x, (0, qp - q))[:, None]

    mu = label_intersect_packed_kernel(
        pad_delta(rows_s.ids), pad_base(rows_s.base), pad_d(rows_s.d),
        pad_delta(rows_t.ids), pad_base(rows_t.base), pad_d(rows_t.d),
        n_sentinel=n_sentinel, bq=bq, interpret=pallas_interpret(backend))
    return mu[:q, 0]
