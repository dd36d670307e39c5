"""Host work per query inside `prune`: the summed seconds of the program's
spans of kind host (numpy or Python passes over arrays of the graph's
size), the outermost where they nest and less the device reads inside
them, over the window's answered queries."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import host_seconds, window_spans  # noqa: E402


def read(record):
    got = window_spans(record)
    if got is None:
        return None
    spans, n = got
    return host_seconds(spans) / n
