"""The least work of one `bitset_spmm` sweep, counted from the graph, the
query and the state alone, and the least time it can take on a chip.

A sweep computes out[v] = OR of vals[u] over the active arcs u -> v, with W
32-bit words per vertex (W = ceil(n0 / 32) for a template of n0 vertices).
Whatever implements it, it has to

- write the result: n rows of W words;
- read the words of every vertex that is the source of an active arc,
  once at least;
- learn which arcs are active: at least one bit per active arc;
- OR one source row into one destination row per active arc: W word
  operations per arc.

So 4 W (n + sources) + ceil(active_arcs / 8) bytes and W active_arcs
operations bound every implementation from below, whatever its block size,
layout or grid. Nothing here reads a block structure.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple


def words_per_vertex(n0: int) -> int:
    return (n0 + 31) // 32


def sweep_work(n: int, words: int, active_arcs: int, sources: int) -> Tuple[int, int]:
    """(bytes, operations) that any implementation of one sweep needs."""
    bytes_needed = 4 * words * (n + sources) + (active_arcs + 7) // 8
    return bytes_needed, words * active_arcs


def least_seconds(bytes_needed: float, ops: float, peak: Dict) -> Tuple[float, str]:
    """(seconds, bound): the larger of bytes over the memory bandwidth and
    operations over the highest operation rate the chip has (int8), and
    which of the two it is."""
    t_bytes = bytes_needed / float(peak["hbm_bytes_per_s"])
    t_ops = ops / float(peak["int8_ops_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")


def load_peak(bench_dir: str, device_kind: str) -> Dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
