"""Helpers of the benchmark's CPU tests. Importing this module puts the
benchmark's modules and the program on the path; `make_tiny_root` makes a
throwaway checkout root holding one tiny cell, made from the real
configuration and traffic files."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

LOCAL = ("g500-s18-blocked", "g500-s18-blocked.hubcycles")
SERVED = ("g500-s20-served", "g500-s20-served.hub8")
TINY_GRAPH = {"scale": 10, "undirected_edges": 10000, "needles": 4, "decoys": 4}
TINY_RARE = 130


def make_tiny_root(dest: str, cell=LOCAL, graph=TINY_GRAPH, rare_max: int = TINY_RARE) -> str:
    """A checkout root whose BENCHMARK.json names one tiny cell `tiny.cell`:
    the configuration and traffic files `cell` = (config, workload) names,
    at a small scale, with the real metrics."""
    config, workload = cell
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    data = os.path.join(dest, "bench")
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(data, sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(data, "metrics"))
    shutil.copytree(os.path.join(BENCH, "policies"), os.path.join(data, "policies"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), data)
    cfg = json.load(open(os.path.join(BENCH, "configs", f"{config}.json")))
    cfg["graph"].update(graph)
    json.dump(cfg, open(os.path.join(data, "configs", "tiny.json"), "w"))
    wl = json.load(open(os.path.join(BENCH, "workloads", f"{workload}.json")))
    wl.update(config="tiny", rare_max_vertices=rare_max)
    json.dump(wl, open(os.path.join(data, "workloads", "tiny.cell.json"), "w"))
    bench["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                         "reduced": ["scale"], "why": "test"}]
    bench["workloads"] = [{"name": "tiny.cell", "config": "tiny", "traffic": "tiny",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    json.dump(bench, open(os.path.join(dest, "BENCHMARK.json"), "w"))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path))


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
