"""Seconds per query of NLCC's edge-prune pass (`core/nlcc.py`): the
durations of the program's `nlcc.edge_prune` spans, its waves, readbacks
and host support build, over the window's answered queries."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import per_query  # noqa: E402


def read(record):
    return per_query(record, lambda s: s.seconds if s.name == "nlcc.edge_prune" else 0.0)
