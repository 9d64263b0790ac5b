"""Segment reductions — the scatter/gather substrate for everything graph.

JAX has no CSR/CSC sparse: the graph passes in this repo (IS-LABEL
construction, wavefront relaxation) are expressed as ``gather ->
elementwise -> segment_*`` over an edge index. These wrappers pin
``num_segments`` static and fix the fill values for empty segments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_sum(data, segment_ids, num_segments: int):
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def segment_min(data, segment_ids, num_segments: int):
    """Min-reduce; empty segments = +inf (float) / dtype max (int)."""
    return jax.ops.segment_min(data, segment_ids, num_segments=num_segments)


def segment_max(data, segment_ids, num_segments: int):
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)


def segment_argmin_take(data, payload, segment_ids, num_segments: int):
    """For each segment return payload of (one) element achieving the min.

    Used for keeping the ``via`` vertex of the min-weight duplicate edge.
    Deterministic: among ties picks the largest payload.
    """
    seg_min = segment_min(data, segment_ids, num_segments)
    is_min = data == seg_min[segment_ids]
    return segment_max(jnp.where(is_min, payload, -1), segment_ids, num_segments)


def count_per_segment(segment_ids, num_segments: int, mask=None):
    ones = jnp.ones(segment_ids.shape, jnp.int32)
    if mask is not None:
        ones = jnp.where(mask, ones, 0)
    return segment_sum(ones, segment_ids, num_segments)
