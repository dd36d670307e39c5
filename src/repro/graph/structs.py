"""Graph data structures.

Host-side `Graph` is numpy (simple, undirected, vertex-labeled, stored as a
directed edge list with both (u,v) and (v,u) present, matching the paper's
"two directed edges represent each undirected edge" convention).

Device-side `DeviceGraph` is a pytree of jnp arrays with edges sorted by
destination — the layout required by the segment-reduce edge sweep that both
the pattern-matching engine and the GNN models use. Metadata (labels) is kept
in a separate array, independent of topology, per the paper's metadata store.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax.numpy as jnp


def unique_pairs(a: np.ndarray, b: np.ndarray):
    """Distinct (a, b) pairs of non-negative ints in lexicographic order — what
    `np.unique(np.stack([a, b], 1), axis=0)` returns, through one int64 key
    sort instead of a row-wise one (seconds instead of minutes at 2^24 pairs)."""
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    if a.size == 0:
        return a, b
    base = int(b.max()) + 1
    keys = np.unique(a * base + b)
    return keys // base, keys % base


@dataclasses.dataclass
class Graph:
    """Host-side labeled graph. Directed edge list; undirected graphs store both arcs."""

    n: int
    src: np.ndarray  # int32[m]
    dst: np.ndarray  # int32[m]
    labels: np.ndarray  # int32[n]

    def __post_init__(self):
        self.src = np.asarray(self.src, dtype=np.int32)
        self.dst = np.asarray(self.dst, dtype=np.int32)
        self.labels = np.asarray(self.labels, dtype=np.int32)
        assert self.labels.shape == (self.n,)
        assert self.src.shape == self.dst.shape

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    @property
    def n_labels(self) -> int:
        return int(self.labels.max()) + 1 if self.n else 0

    @staticmethod
    def from_undirected_pairs(n: int, pairs, labels) -> "Graph":
        """Build from unique undirected pairs (u < v); adds both arcs, dedups, drops self-loops."""
        pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
        pairs = pairs[pairs[:, 0] != pairs[:, 1]]
        both = np.concatenate([pairs, pairs[:, ::-1]], axis=0)
        src, dst = unique_pairs(both[:, 0], both[:, 1])
        return Graph(n=n, src=src, dst=dst, labels=np.asarray(labels))

    def csr(self):
        """Return (offsets int64[n+1], neighbors int32[m]) sorted by (src, dst)."""
        order = np.lexsort((self.dst, self.src))
        s, d = self.src[order], self.dst[order]
        offsets = np.zeros(self.n + 1, dtype=np.int64)
        np.add.at(offsets, s + 1, 1)
        np.cumsum(offsets, out=offsets)
        return offsets, d

    def degrees(self) -> np.ndarray:
        deg = np.zeros(self.n, dtype=np.int64)
        np.add.at(deg, self.src, 1)
        return deg

    def label_frequency(self) -> np.ndarray:
        """freq[l] = number of vertices with label l (paper's token-ordering heuristic input)."""
        return np.bincount(self.labels, minlength=self.n_labels)

    def subgraph(self, vmask: np.ndarray, emask: Optional[np.ndarray] = None) -> "Graph":
        """Induced subgraph on active vertices (and optionally active edges), re-indexed."""
        vmask = np.asarray(vmask, dtype=bool)
        keep = vmask[self.src] & vmask[self.dst]
        if emask is not None:
            keep &= np.asarray(emask, dtype=bool)
        new_id = np.cumsum(vmask, dtype=np.int64) - 1
        return Graph(
            n=int(vmask.sum()),
            src=new_id[self.src[keep]],
            dst=new_id[self.dst[keep]],
            labels=self.labels[vmask],
        )

    def validate_undirected(self) -> bool:
        fw = set(zip(self.src.tolist(), self.dst.tolist()))
        return all((d, s) in fw for (s, d) in fw)


@dataclasses.dataclass
class DeviceGraph:
    """Device-side graph in dst-sorted COO layout (+ labels). A pytree of jnp arrays.

    Edges are sorted by dst so per-destination aggregation is a segment reduce over
    contiguous runs — the layout the `bitset_spmm` / `segment_agg` kernels tile.
    """

    n: int
    src: jnp.ndarray  # int32[m] sorted by dst
    dst: jnp.ndarray  # int32[m]
    labels: jnp.ndarray  # int32[n]

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    @staticmethod
    def dst_sort_order(g: Graph) -> np.ndarray:
        """The dst-sort permutation `from_host` applies — exposed so callers
        that also need the order (e.g. the sharded backends' edge gather map)
        compute it once and stay in sync with this layout."""
        return np.lexsort((g.src, g.dst))

    @staticmethod
    def from_host(g: Graph, order: Optional[np.ndarray] = None) -> "DeviceGraph":
        if order is None:
            order = DeviceGraph.dst_sort_order(g)
        return DeviceGraph(
            n=g.n,
            src=jnp.asarray(g.src[order]),
            dst=jnp.asarray(g.dst[order]),
            labels=jnp.asarray(g.labels),
        )

    def tree_flatten(self):
        return (self.src, self.dst, self.labels), self.n

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, dst, labels = children
        return cls(n=aux, src=src, dst=dst, labels=labels)


import jax.tree_util as _jtu  # noqa: E402

_jtu.register_pytree_node(
    DeviceGraph, DeviceGraph.tree_flatten, DeviceGraph.tree_unflatten
)
