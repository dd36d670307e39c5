"""The benchmark's Graph500 generator: determinism per seed, the degree
label rule, the planted needles and decoys, and the arc layout."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

import numpy as np

import graph500

KW = dict(scale=9, edge_factor=16, abcd=(0.57, 0.19, 0.19, 0.05),
          undirected_edges=4000, needles=3, decoys=2)


def _host(g):
    return np.asarray(g.src), np.asarray(g.dst), np.asarray(g.labels)


def test_same_seed_same_graph_other_seed_other_graph():
    a, b, c = (graph500.generate(s, **KW) for s in (7, 7, 8))
    for x, y in zip(_host(a), _host(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(np.asarray(a.src), np.asarray(c.src))
    big = graph500.generate(2**33 + 5, **KW)       # seeds past 32 bits
    assert big.m == a.m and not np.array_equal(np.asarray(big.src), np.asarray(a.src))


def test_shapes_fixed_arcs_sorted_symmetric_simple():
    g = graph500.generate(3, **KW)
    src, dst, labels = _host(g)
    n_bg = 1 << KW["scale"]
    assert g.n == n_bg + 4 * KW["needles"] + 8 * KW["decoys"] == labels.shape[0]
    edges = KW["undirected_edges"] + 5 * KW["needles"] + 9 * KW["decoys"]
    assert g.m == 2 * edges
    assert np.all(src != dst)
    key = dst.astype(np.int64) * g.n + src
    assert np.all(np.diff(key) > 0)                  # (dst, src) sorted, no repeats
    fwd = set(zip(src.tolist(), dst.tolist()))
    assert all((d, s) in fwd for s, d in fwd)


def test_degree_labels_and_planted_structures():
    g = graph500.generate(5, **KW)
    src, dst, labels = _host(g)
    n_bg = 1 << KW["scale"]
    bg = (src < n_bg) & (dst < n_bg)
    deg = np.bincount(src[bg], minlength=n_bg)
    want = np.ceil(np.log2(deg + 1)).astype(np.int32)
    np.testing.assert_array_equal(labels[:n_bg], want)
    top = int(want.max())
    assert g.needle_labels == (top + 1, top + 2, top + 2, top + 1)
    planted = labels[n_bg:]
    assert set(planted.tolist()) == {top + 1, top + 2}
    assert np.count_nonzero(planted == top + 1) == 2 * KW["needles"] + 4 * KW["decoys"]
    assert int(g.label_freq[top + 1]) == 2 * KW["needles"] + 4 * KW["decoys"]
    # each planted vertex: two ring neighbours, plus one background anchor
    # for the first vertex of each copy
    deg_all = np.bincount(src, minlength=g.n)[n_bg:]
    firsts = [4 * k for k in range(KW["needles"])] + [
        4 * KW["needles"] + 8 * k for k in range(KW["decoys"])]
    np.testing.assert_array_equal(np.delete(deg_all, firsts), 2)
    np.testing.assert_array_equal(deg_all[firsts], 3)
