"""Reduction of a JAX profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy time, each device operation's summed time,
and the longest idle gaps with the host span that was open during each.

Busy time is the union of the intervals of the operations on each device
plane ("XLA Ops" line), averaged over the devices. The window is the host
span named `window_span`. Host spans are the `bench.*` annotations of
`spans.py`.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _stats_text(ev) -> str:
    return " ".join(f"{k}={v}" for k, v in ev.stats)


def reduce_planes(planes, window_span: str = "bench.window") -> Dict:
    """`planes`: iterable of objects with `.name` and `.lines`, each line with
    `.name` and `.events` (`.name`, `.start_ns`, `.duration_ns`, `.stats`),
    as `jax.profiler.ProfileData` gives them."""
    host_spans: List[Tuple[int, int, str]] = []
    device_ops: Dict[str, List[Tuple[int, int, str]]] = {}
    op_text: Dict[str, str] = {}
    for plane in planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = device_ops.setdefault(plane.name, [])
                for ev in line.events:
                    s = int(ev.start_ns)
                    evs.append((s, s + int(ev.duration_ns), ev.name))
                    if ev.name not in op_text:
                        op_text[ev.name] = _stats_text(ev)[:2000]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        host_spans.append((s, s + int(ev.duration_ns), ev.name))
    windows = [(s, e) for s, e, n in host_spans if n == window_span]
    if not windows:
        raise ValueError(f"the trace holds no {window_span!r} span")
    w0, w1 = windows[0]
    if not device_ops:
        raise ValueError("the trace holds no device operations")

    busy_ns = []
    op_total: Dict[str, List[int]] = {}
    gaps: List[Tuple[int, int]] = []
    for k, (plane, evs) in enumerate(sorted(device_ops.items())):
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in evs if e > w0 and s < w1]
        merged = _merge([(s, e) for s, e, _ in inside])
        busy_ns.append(sum(e - s for s, e in merged))
        for s, e, n in inside:
            tot = op_total.setdefault(n, [0, 0])
            tot[0] += e - s
            tot[1] += 1
        if k == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]

    def host_at(t: int) -> str:
        best = None
        for s, e, n in host_spans:
            if s <= t < e and n != window_span and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "bench.window"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    idle_gaps = [[host_at((s + e) // 2), (e - s) / 1e9] for s, e in gaps[:10]]
    top_ops = sorted(op_total.items(), key=lambda kv: kv[1][0], reverse=True)
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "n_devices": len(busy_ns),
        "op_seconds": {n: t / 1e9 / len(busy_ns) for n, (t, _) in op_total.items()},
        "op_counts": {n: c for n, (_, c) in op_total.items()},
        "op_text": {n: op_text.get(n, "") for n in op_total},
        "breakdown": {
            "device_ops": [[n, t / 1e9 / len(busy_ns)] for n, (t, _) in top_ops[:10]],
            "idle_gaps": idle_gaps,
        },
    }


def reduce_file(path: str, window_span: str = "bench.window") -> Dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_span)
