"""Label compression codecs (``IndexConfig.label_dtype``).

The query hot path is memory-bound on the label planes: every batch
gathers four ``[Q, l_cap]`` rows (two id rows, two distance rows) out of
HBM before any compute happens. Pruned Landmark Labeling and Hop
Doubling both report label size as the binding constraint at scale, so
the index can store the planes compressed and let the kernels decode
in-register:

``delta16`` id codec
    Sorted ancestor-id rows become one ``int32`` base (the first id)
    plus ``int16`` forward deltas — 2 bytes/entry instead of 4.
    Padding slots (id == n sentinel) are marked in-band with a
    ``-1`` delta; decode maps every slot at or after the first marker
    back to the sentinel, so decoded rows stay sorted (the searchsorted
    reference still works) and the ``ids < n`` masks behave identically.
    Rows whose real-entry deltas exceed ``int16`` don't fit — the codec
    refuses (``label_dtype="compressed"`` raises; ``"auto"`` falls back
    to fp32).

``int32`` distance codec
    When every finite label distance is a non-negative integer below
    2**24, distances are stored as ``int32`` (``-1`` marks +inf pads)
    and decoded by exact int->fp32 conversion — **bitwise** identical
    to the uncompressed pipeline, not merely ULP-close. Non-integral
    weights keep fp32 distances (ids still compress); then the decoded
    values are the original fp32 bits anyway, so end-to-end answers
    remain bitwise too. The ULP gate in tests exists as the contract
    for future lossy codecs; delta16/int32 are exact by construction.

Decode (``decode_ids``/``decode_rows``) is pure jnp so the same code
runs inside the Pallas ``label_intersect`` kernel, the interpret
backend, the jnp reference, and the seed scatter of stage 2.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

__all__ = [
    "LabelRows", "LabelCompressionError", "encode_labels",
    "try_encode_labels", "decode_ids", "decode_d", "decode_rows",
    "encoded_nbytes",
]

DELTA_MAX = np.int64(2 ** 15 - 1)     # int16 ceiling for a real delta
D_INT_MAX = float(2 ** 24)            # int32 -> fp32 conversion stays exact
PAD_DELTA = -1                        # in-band pad marker (real deltas >= 0)
PAD_D = -1                            # +inf distance marker in int32 planes


class LabelCompressionError(ValueError):
    """The label planes don't fit the requested codec (delta overflow,
    unsorted rows, or non-integral distances under d_dtype=int32)."""


class LabelRows(NamedTuple):
    """Gathered label rows as the dispatch layer consumes them.

    codec "none":    ids int32[..., L], base None,         d float32
    codec "delta16": ids int16[..., L] (deltas), base int32[...],
                     d int32 (integral weights) or float32
    """
    ids: jnp.ndarray
    base: jnp.ndarray | None
    d: jnp.ndarray


# --------------------------------------------------------------- encode
def encode_labels(ids, d, n_sentinel: int, d_dtype: str | None = None):
    """Host-side delta16 encode of ``[..., L]`` label planes.

    Returns ``(delta int16, base int32, d_enc int32|float32)``.
    ``d_dtype``: None infers int32 vs float32 from the data; "int32" /
    "float32" pin the distance plane dtype (families need a fixed
    dtype across versions) and raise if the data doesn't fit.
    """
    ids = np.asarray(ids)
    d = np.asarray(d, np.float32)
    if ids.shape != d.shape or ids.shape[-1] == 0:
        raise LabelCompressionError(f"bad label plane shape {ids.shape}")
    real = ids < n_sentinel
    # rows must be [real entries..., pads] — the layout labeling.py and
    # every host mutator maintain
    if (real[..., 1:] & ~real[..., :-1]).any():
        raise LabelCompressionError("non-contiguous pad slots in a row")
    step = np.diff(ids.astype(np.int64), axis=-1)
    realpair = real[..., 1:]            # contiguity: real[j] => real[j-1]
    if realpair.any():
        real_steps = step[realpair]
        if real_steps.min(initial=0) < 0:
            raise LabelCompressionError("unsorted label row")
        if real_steps.max(initial=0) > DELTA_MAX:
            raise LabelCompressionError(
                f"ancestor-id delta {int(real_steps.max())} exceeds int16")
    delta = np.full(ids.shape, PAD_DELTA, np.int16)
    delta[..., 0] = np.where(real[..., 0], 0, PAD_DELTA)
    delta[..., 1:] = np.where(realpair, step, PAD_DELTA).astype(np.int16)
    base = np.where(real[..., 0], ids[..., 0], 0).astype(np.int32)

    vals = d[real]
    integral = (vals.size == 0 or
                (np.isfinite(vals).all() and (vals >= 0).all()
                 and (vals < D_INT_MAX).all()
                 and (vals == np.round(vals)).all()))
    if d_dtype == "int32" and not integral:
        raise LabelCompressionError(
            "non-integral/oversized distance under pinned int32 codec")
    if d_dtype == "float32" or (d_dtype is None and not integral):
        d_enc = d.copy()
    else:
        d_enc = np.where(real, d, float(PAD_D)).astype(np.int32)
    return delta, base, d_enc


def try_encode_labels(ids, d, n_sentinel: int, d_dtype: str | None = None):
    """``encode_labels`` or None when the planes don't fit the codec."""
    try:
        return encode_labels(ids, d, n_sentinel, d_dtype)
    except LabelCompressionError:
        return None


def encoded_nbytes(delta, base, d_enc) -> int:
    return int(np.asarray(delta).nbytes + np.asarray(base).nbytes
               + np.asarray(d_enc).nbytes)


# --------------------------------------------------------------- decode
def _cumsum_last(x):
    return jnp.cumsum(x, axis=-1)


def decode_ids(delta, base, n_sentinel: int, cumsum=_cumsum_last):
    """int16 deltas + int32 base -> sorted int32 ids (pads -> sentinel).

    ``base`` holds one id per row, as ``[...]`` or ``[..., 1]``. Pure
    jnp over an inclusive prefix sum along the last axis, so it runs
    unchanged in the interpret backend, the reference, and — given the
    kernel's lane prefix sum as ``cumsum`` (Mosaic lowers no cumsum) —
    inside the Pallas kernel body.
    """
    delta = delta.astype(jnp.int32)
    if base.ndim < delta.ndim:
        base = base[..., None]
    pad = cumsum((delta < 0).astype(jnp.int32)) > 0
    steps = jnp.where(pad, 0, delta)
    ids = base.astype(jnp.int32) + cumsum(steps)
    return jnp.where(pad, jnp.int32(n_sentinel), ids)


def decode_d(d_enc):
    """int32 distance plane -> float32 (exact below 2**24); fp32 planes
    pass through untouched."""
    if d_enc.dtype == jnp.float32:
        return d_enc
    return jnp.where(d_enc < 0, jnp.inf, d_enc.astype(jnp.float32))


def decode_rows(rows: LabelRows, n_sentinel: int, codec: str):
    """(ids int32, d float32) for either codec — the seed scatter and
    the reference backend consume this."""
    if codec == "none":
        return rows.ids, rows.d
    return decode_ids(rows.ids, rows.base, n_sentinel), decode_d(rows.d)
