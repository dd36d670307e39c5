"""LCC fixpoint seconds per query: the "LCC" entries of `prune`'s phase
trajectory (fenced by the backend's sync), summed over the window's
answered queries and divided by their number."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _phases import mean_per_query  # noqa: E402


def read(record):
    return mean_per_query(record, lambda name: name == "LCC")
