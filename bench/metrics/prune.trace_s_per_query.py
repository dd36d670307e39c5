"""Seconds per query that JAX spent inside `prune` tracing, lowering and
compiling programs or loading them from the compile cache: the `trace_s`
counters of the program's spans, over the window's answered queries."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import per_query  # noqa: E402


def read(record):
    return per_query(record, lambda s: s.counters.get("trace_s", 0.0))
