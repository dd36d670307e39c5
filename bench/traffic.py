"""Query traffic from a workload file and a seed.

A workload file (`bench/workloads/<cell>.json`) gives the mix as data:

    shapes             template shapes in equal shares, in this order
                       round after round: "needle" (the planted 4-cycle),
                       "cycle<k>" or "path<k>" (k distinct labels)
    rare_max_vertices  a label is rare when at most this many vertices of
                       the cell's own graph carry it; cycle and path labels
                       are drawn from the rare labels
    queries            length of the list (a window that reaches its end
                       starts it again)
    clients            closed-loop analysts, each submitting its next query
                       when its last one is answered
    labels_seed        draws the label set of every template

Every seed gets the same list of shapes and label sets, so that its window
holds the same work; the run's seed (besides drawing the graph) draws where
each template's walk starts and its direction: the same template, its
vertices numbered in another order.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import List, Sequence, Tuple

import numpy as np

from graph500 import NEEDLE_EDGES


@dataclasses.dataclass(frozen=True)
class Query:
    shape: str
    labels: Tuple[int, ...]
    edges: Tuple[Tuple[int, int], ...]


def load_workload(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "workloads", f"{name}.json")) as f:
        return json.load(f)


def rare_labels(label_freq: np.ndarray, max_vertices: int, exclude=()) -> List[int]:
    return [int(lab) for lab, f in enumerate(label_freq)
            if 0 < f <= max_vertices and lab not in exclude]


def shape_edges(shape: str) -> Tuple[int, Tuple[Tuple[int, int], ...]]:
    """(vertex count, walk-order edges) of a "cycle<k>" or "path<k>" shape."""
    m = re.fullmatch(r"(cycle|path)(\d+)", shape)
    if m is None:
        raise ValueError(f"unknown template shape {shape!r}")
    k = int(m.group(2))
    path = tuple((i, i + 1) for i in range(k - 1))
    if m.group(1) == "path":
        return k, path
    if k < 3:
        raise ValueError("a cycle needs at least 3 vertices")
    return k, path + ((k - 1, 0),)


def make_queries(workload: dict, seed: int, label_freq: np.ndarray,
                 needle_labels: Sequence[int]) -> List[Query]:
    rare = rare_labels(label_freq, int(workload["rare_max_vertices"]),
                       exclude=set(needle_labels))
    sets = np.random.default_rng(int(workload["labels_seed"]))
    order = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
    shapes = list(workload["shapes"])
    out = []
    for i in range(int(workload["queries"])):
        shape = shapes[i % len(shapes)]
        if shape == "needle":
            out.append(Query(shape, tuple(int(x) for x in needle_labels), NEEDLE_EDGES))
            continue
        k, edges = shape_edges(shape)
        if len(rare) < k:
            raise ValueError(f"{shape} needs {k} distinct rare labels; the graph has {rare}")
        labels = [int(x) for x in sets.choice(rare, size=k, replace=False)]
        if shape.startswith("cycle"):
            start = int(order.integers(k))
            labels = labels[start:] + labels[:start]
        if order.integers(2):
            labels = labels[::-1]
        out.append(Query(shape, tuple(labels), edges))
    return out
