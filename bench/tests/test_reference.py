"""The plain reference against the program's brute-force enumerator
(`repro.core.oracle`) and the pruning pipeline, on a small generated graph."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

import numpy as np
import pytest

import graph500
import reference
import traffic

from repro.core.oracle import enumerate_matches_bruteforce
from repro.core.pipeline import prune
from repro.core.template import Template
from repro.graph.structs import DeviceGraph, Graph

KW = dict(scale=9, edge_factor=16, abcd=(0.57, 0.19, 0.19, 0.05),
          undirected_edges=4000, needles=3, decoys=2)


@pytest.fixture(scope="module")
def graph():
    return graph500.generate(11, **KW)


def _union_from_oracle(g, src, dst, labels, t_labels, t_edges):
    host = Graph(n=g.n, src=src, dst=dst, labels=labels)
    emb = enumerate_matches_bruteforce(host, Template(list(t_labels), list(t_edges)))
    vm = np.zeros(g.n, bool)
    keys = set()
    for phi in emb:
        vm[list(phi)] = True
        for a, b in t_edges:
            keys.add((phi[a], phi[b]))
            keys.add((phi[b], phi[a]))
    am = np.array([(s, d) in keys for s, d in zip(src.tolist(), dst.tolist())])
    return vm, am


def test_reference_equals_brute_force_and_pipeline(graph):
    src, dst, labels = (np.asarray(x) for x in (graph.src, graph.dst, graph.labels))
    wl = {"shapes": ["needle", "cycle3", "cycle4", "path4"], "labels_seed": 3,
          "rare_max_vertices": 75, "queries": 8}
    qs = traffic.make_queries(wl, 4, graph.label_freq, graph.needle_labels)
    dg = DeviceGraph(n=graph.n, src=graph.src, dst=graph.dst, labels=graph.labels)
    nonempty = 0
    for q in qs:
        rv, ra = reference.union_of_matches(src, dst, labels, q.labels, q.edges)
        ov, oa = _union_from_oracle(graph, src, dst, labels, q.labels, q.edges)
        np.testing.assert_array_equal(rv, ov)
        np.testing.assert_array_equal(ra, oa)
        res = prune(dg, Template(list(q.labels), list(q.edges)),
                    label_freq=graph.label_freq, wave=32, nlcc_edge_prune=True)
        np.testing.assert_array_equal(res.vertex_mask, rv)
        np.testing.assert_array_equal(res.edge_mask, ra)
        nonempty += int(rv.any())
    assert nonempty >= 4


def test_decoys_are_not_matches(graph):
    src, dst, labels = (np.asarray(x) for x in (graph.src, graph.dst, graph.labels))
    rv, ra = reference.union_of_matches(src, dst, labels, graph.needle_labels,
                                        graph500.NEEDLE_EDGES)
    n_bg = 1 << KW["scale"]
    assert np.flatnonzero(rv).tolist() == list(range(n_bg, n_bg + 4 * KW["needles"]))
    assert int(ra.sum()) == 8 * KW["needles"]


def test_reference_refuses_what_it_does_not_cover():
    with pytest.raises(NotImplementedError):
        reference.template_kind([1, 2, 1], [(0, 1), (1, 2)])    # a-b-a path
    with pytest.raises(NotImplementedError):
        reference.template_kind([1, 2, 3, 4], [(0, 1), (0, 2), (0, 3)])   # a star
