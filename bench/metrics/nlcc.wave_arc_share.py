"""Share of the whole graph's arcs the NLCC wave hops step over, in %: over
the window's `nlcc.wave_loop` spans (one a constraint), the arcs of the
graph each hop runs on (`wave_arcs`: the compacted active subgraph's arc
capacity, or m where the waves run on the whole graph) over m for the same
hops (`graph_arcs`). Lower is fewer arcs stepped over that cannot carry a
token; 100 where no constraint's waves were compacted."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import window_spans  # noqa: E402


def read(record):
    got = window_spans(record)
    if got is None:
        return None
    loops = [s.counters for s in got[0] if s.name == "nlcc.wave_loop"
             and "wave_arcs" in s.counters and "graph_arcs" in s.counters]
    graph = sum(c["graph_arcs"] for c in loops)
    if graph == 0:
        return None
    return 100.0 * sum(c["wave_arcs"] for c in loops) / graph
