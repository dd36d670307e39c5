"""Share of the arcs the LCC sweeps step through that are still active, in
%: over the window's `lcc.fixpoint` spans, the active arcs at the start of
every sweep (`active_arcs`) over the arcs the sweeps' per-arc passes go over
(`stepped_arcs`, every arc in every sweep). Higher is less stepping over
arcs that are already gone: a sweep that changes little, or a pass over
arcs the fixpoint has removed, lowers it."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import window_spans  # noqa: E402


def read(record):
    got = window_spans(record)
    if got is None:
        return None
    fix = [s.counters for s in got[0] if s.name == "lcc.fixpoint"
           and "active_arcs" in s.counters and "stepped_arcs" in s.counters]
    stepped = sum(c["stepped_arcs"] for c in fix)
    if stepped == 0:
        return None
    return 100.0 * sum(c["active_arcs"] for c in fix) / stepped
