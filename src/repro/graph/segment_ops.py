"""Segment reduction primitives over edge indices.

JAX sparse support is BCOO-only, so message passing here IS the system:
gather endpoint features with `jnp.take`, reduce by destination with
`jax.ops.segment_*`. The pattern-matching engine (bitwise OR over packed
candidate words) and every GNN aggregator route through these.

Bitwise OR has no native XLA scatter combiner, so `segment_or` uses a
*segmented doubling scan* over dst-sorted edges with host-precomputed
segment boundaries (static per graph). On TPU the `bitset_spmm` Pallas kernel
replaces this path with a single VMEM-tiled edge sweep.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp


class SegmentMeta(NamedTuple):
    """Static metadata for dst-sorted edge arrays (host-precomputed)."""

    is_start: jnp.ndarray  # bool[m]  edge i starts a new dst segment
    last_edge_of_vertex: jnp.ndarray  # int32[n]  index of v's last in-edge, -1 if none


def build_segment_meta(dst_sorted: np.ndarray, n: int) -> SegmentMeta:
    dst_sorted = np.asarray(dst_sorted)
    m = dst_sorted.shape[0]
    if m == 0:
        return SegmentMeta(
            is_start=jnp.zeros((0,), bool),
            last_edge_of_vertex=jnp.full((n,), -1, jnp.int32),
        )
    is_start = np.ones(m, dtype=bool)
    is_start[1:] = dst_sorted[1:] != dst_sorted[:-1]
    last = np.full(n, -1, dtype=np.int32)
    last[dst_sorted] = np.arange(m, dtype=np.int32)  # later writes win = last edge
    return SegmentMeta(is_start=jnp.asarray(is_start), last_edge_of_vertex=jnp.asarray(last))


def _seg_or_op(a, b):
    va, fa = a
    vb, fb = b
    return jnp.where(fb, vb, va | vb), fa | fb


def segment_or(values: jnp.ndarray, meta: SegmentMeta, num_segments: int) -> jnp.ndarray:
    """OR-reduce uint words [m, W] by destination -> [num_segments, W].

    `values` must be ordered like the dst-sorted edge array `meta` was built from.
    """
    m = values.shape[0]
    if m == 0:
        return jnp.zeros((num_segments,) + values.shape[1:], values.dtype)
    trail = values.shape[1:]
    flags = meta.is_start.reshape((m,) + (1,) * len(trail))
    # Hillis-Steele doubling scan: ceil(log2 m) static shifts, each combining
    # element i with element i - d under _seg_or_op. `associative_scan`'s
    # strided odd/even slices cost the TPU compiler time and host memory
    # linear in m (minutes and tens of GB at 31 M arcs); contiguous shifts
    # compile in seconds at any m.
    scanned, d = values, 1
    while d < m:
        prev = (jnp.concatenate([jnp.zeros((d,) + trail, values.dtype), scanned[:-d]]),
                jnp.concatenate([jnp.ones((d,) + flags.shape[1:], bool), flags[:-d]]))
        scanned, flags = _seg_or_op(prev, (scanned, flags))
        d *= 2
    idx = meta.last_edge_of_vertex
    out = jnp.take(scanned, jnp.clip(idx, 0, m - 1), axis=0)
    mask = (idx >= 0).reshape((num_segments,) + (1,) * (values.ndim - 1))
    return jnp.where(mask, out, jnp.zeros_like(out))


def segment_or_bool(values: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int,
                    sorted: bool = True) -> jnp.ndarray:
    """Boolean-plane OR-reduce (reference path; 8x the bytes of the packed path).

    Note: segment_max yields INT_MIN for empty segments, so compare > 0 rather
    than casting — empty segments must aggregate to False.
    """
    return jax.ops.segment_max(
        values.astype(jnp.int32), segment_ids, num_segments=num_segments,
        indices_are_sorted=sorted,
    ) > 0


def segment_sum(values, segment_ids, num_segments, sorted: bool = True):
    return jax.ops.segment_sum(
        values, segment_ids, num_segments=num_segments, indices_are_sorted=sorted
    )


def segment_max(values, segment_ids, num_segments, sorted: bool = True):
    return jax.ops.segment_max(
        values, segment_ids, num_segments=num_segments, indices_are_sorted=sorted
    )


def segment_min(values, segment_ids, num_segments, sorted: bool = True):
    return jax.ops.segment_min(
        values, segment_ids, num_segments=num_segments, indices_are_sorted=sorted
    )


def segment_count(segment_ids, num_segments, sorted: bool = True, dtype=jnp.float32):
    return segment_sum(
        jnp.ones(segment_ids.shape[:1], dtype), segment_ids, num_segments, sorted
    )


def segment_mean(values, segment_ids, num_segments, sorted: bool = True):
    s = segment_sum(values, segment_ids, num_segments, sorted)
    cnt = segment_count(segment_ids, num_segments, sorted, values.dtype)
    return s / jnp.maximum(cnt, 1).reshape((-1,) + (1,) * (values.ndim - 1))


def segment_softmax(scores, segment_ids, num_segments, sorted: bool = True):
    """Edge-softmax (GAT): softmax over edges grouped by destination."""
    mx = segment_max(scores, segment_ids, num_segments, sorted)
    ex = jnp.exp(scores - jnp.take(mx, segment_ids, axis=0))
    den = segment_sum(ex, segment_ids, num_segments, sorted)
    return ex / jnp.maximum(jnp.take(den, segment_ids, axis=0), 1e-16)
