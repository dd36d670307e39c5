"""One run of one cell: set-up, warm-up, the measured window, the check
against the reference, and the result line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is data found by name: `BENCHMARK.json` at the root of the
checkout lists them, `bench/configs/<config>.json` holds a configuration,
`bench/workloads/<cell>.json` a traffic mix and `bench/metrics/<metric>.py`
the reader of a per-layer metric (a function `read(record)` that returns a
number, or None where the run has nothing to read).

The window opens at the first submission after warm-up. Clients submit
until `seconds` have passed; the window closes when the last query in
flight is answered, so every query submitted in it is counted.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import jax

import drivers
import reference
import work
import xplane
from spans import span
from traffic import load_workload, make_queries


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ lookup
def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_entries(bench: dict, cell: str) -> Tuple[dict, dict]:
    """(workload entry, config entry) of BENCHMARK.json for a cell."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if cell not in by_name:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    w = by_name[cell]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return w, cfg


def metrics_for(bench: dict, cell: str, kind: str) -> List[dict]:
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def load_reader(bench_dir: str, name: str) -> Callable:
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ window
def quantile(values: List[float], q: float) -> float:
    """The q-quantile by linear interpolation between order statistics."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    pos = q * (len(v) - 1)
    lo = int(np.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def window_metrics(records: List[dict], window_s: float) -> Dict[str, float]:
    """End-to-end numbers of one window: latency quantiles over every
    query answered in it, and answers per second over the whole window."""
    lat = [r["done"] - r["submit"] for r in records if r["status"] == "ok"]
    return {
        "query_p50_s": quantile(lat, 0.5),
        "query_p95_s": quantile(lat, 0.95),
        "queries_per_s": len(lat) / window_s,
    }


def run_closed_loop_single(driver, queries, seconds: float, records: List[dict],
                           clock=time.perf_counter) -> Tuple[float, float]:
    """One analyst: the next query goes in when the last one is answered.
    Returns (window start, window end)."""
    t0 = clock()
    i = 0
    while i == 0 or clock() - t0 < seconds:
        q = queries[i % len(queries)]
        rec = {"i": i, "shape": q.shape, "labels": list(q.labels),
               "edges": [list(e) for e in q.edges], "submit": clock()}
        try:
            ans = driver.answer(q)
            rec.update(status=ans.status, answer=ans)
        except Exception as e:  # a query that raises is failed, not fatal
            rec.update(status="error", error=f"{type(e).__name__}: {e}")
        rec["done"] = clock()
        records.append(rec)
        i += 1
    return t0, records[-1]["done"]


def run_closed_loop_batched(driver, queries, clients: int, seconds: float,
                            records: List[dict], pumps: List[dict],
                            clock=time.perf_counter) -> Tuple[float, float]:
    """`clients` analysts, each re-submitting when answered; the engine's
    `pump` launches due batches. After `seconds` no client submits again
    and the queries in flight are drained."""
    t0 = clock()
    nxt = 0
    idle = list(range(clients))
    inflight: Dict[int, dict] = {}
    while True:
        open_ = clock() - t0 < seconds
        if open_:
            for c in idle:
                q = queries[nxt % len(queries)]
                rec = {"i": nxt, "client": c, "shape": q.shape, "labels": list(q.labels),
                       "edges": [list(e) for e in q.edges], "submit": clock()}
                try:
                    driver.submit(q, nxt)
                    inflight[nxt] = rec
                except Exception as e:
                    rec.update(status="error", error=f"{type(e).__name__}: {e}", done=clock())
                    records.append(rec)
                nxt += 1
            idle = []
        if not inflight:
            break
        p0 = clock()
        try:
            got = driver.pump(force=not open_)
        except Exception as e:  # the batch failed: every query in it is failed
            now = clock()
            for tag, rec in list(inflight.items()):
                rec.update(status="error", error=f"{type(e).__name__}: {e}", done=now)
                records.append(rec)
                idle.append(rec["client"])
            inflight.clear()
            continue
        p1 = clock()
        if got:
            pumps.append({"seconds": p1 - p0, "batches": sorted(
                {a.batch for _, a in got if a.batch is not None}),
                "batch_seconds": {a.batch: a.counters["batch_seconds"]
                                  for _, a in got if a.counters}})
        for tag, ans in got:
            rec = inflight.pop(tag)
            rec.update(status=ans.status, answer=ans, done=p1)
            records.append(rec)
            idle.append(rec["client"])
        if not got and open_:
            time.sleep(0.001)
    records.sort(key=lambda r: r["i"])
    return t0, max(r["done"] for r in records)


# ------------------------------------------------------------------ check
def check_answers(records: List[dict], src: np.ndarray, dst: np.ndarray,
                  labels: np.ndarray) -> Dict:
    """Compare every answered query with the plain reference: wrong
    vertices and wrong arcs, summed over the queries."""
    bad_v = bad_a = checked = 0
    for r in records:
        if r["status"] != "ok":
            continue
        ans = r["answer"]
        rv, ra = reference.union_of_matches(src, dst, labels, r["labels"], r["edges"])
        gv = np.zeros(labels.shape[0], bool)
        gv[ans.vertices] = True
        ga = np.zeros(src.shape[0], bool)
        ga[ans.arcs] = True
        bad_v += int(np.sum(gv != rv))
        bad_a += int(np.sum(ga != ra))
        checked += 1
    return {"wrong_vertices": bad_v, "wrong_arcs": bad_a, "checked": checked}


# ------------------------------------------------------------------ run
def run(cell: str, seed: int, seconds: float, traced: bool, *,
        root: str, device: dict, patch: Optional[Callable] = None) -> dict:
    """One run of `cell`; returns the result object of the last line.
    `patch(driver)`, where given, swaps part of the timed path after set-up:
    the control and the fault tests put a broken path in this way."""
    bench = load_benchmark(root)
    data_dir = os.path.join(root, bench["paths"][0])
    w_entry, c_entry = cell_entries(bench, cell)
    with open(os.path.join(root, c_entry["file"])) as f:
        cfg = json.load(f)
    workload = load_workload(data_dir, cell)
    t_setup = time.perf_counter()
    parts: Dict[str, float] = {}
    driver = drivers.DRIVERS[cfg["engine"]](cfg, seed, data_dir, parts)
    if patch is not None:
        patch(driver)
    g = driver.graph
    queries = make_queries(workload, seed, g.label_freq, g.needle_labels)
    log(f"[set-up] {cell}: n={g.n} m={g.m} {driver.info}, {len(queries)} queries "
        f"in the list, shapes {workload['shapes']}")
    with span("bench.warmup", parts):
        _warm(driver, queries, int(workload["clients"]))
    setup_s = time.perf_counter() - t_setup
    for k, v in parts.items():
        log(f"[set-up] {k}: {v:.3f} s")
    log(f"[set-up] setup_s {setup_s:.3f} s")

    compiles = _CompileCounter()
    records: List[dict] = []
    pumps: List[dict] = []
    trace_dir = os.path.join(data_dir, ".trace", cell)
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    with compiles, span("bench.window"):
        if workload["clients"] == 1 and cfg["engine"] == "local_blocked":
            w0, w1 = run_closed_loop_single(driver, queries, seconds, records)
        else:
            w0, w1 = run_closed_loop_batched(driver, queries, workload["clients"],
                                             seconds, records, pumps)
    if traced:
        jax.profiler.stop_trace()
    window_s = w1 - w0
    ok = [r for r in records if r["status"] == "ok"]
    failed = len(records) - len(ok)
    log(f"[window] {window_s:.3f} s, {len(records)} queries submitted, {len(ok)} answered, "
        f"{failed} failed; overrun past {seconds} s: {max(0.0, window_s - seconds):.3f} s; "
        f"programs compiled inside the window: {compiles.n} "
        f"({compiles.loaded} more loaded from the compile cache)")
    for r in records:
        if r["status"] != "ok":
            log(f"[window] query {r['i']} {r['shape']} {r['labels']}: {r.get('error', r['status'])}")
            continue
        ans = r["answer"]
        phases = [(p, round(s, 3)) for p, s in ans.phases or []]
        log(f"[window] query {r['i']} {r['shape']} {r['labels']}: "
            f"{r['done'] - r['submit']:.3f} s, phases {phases}, counters {ans.counters}")
    peak = _memory_peak()

    summary = None
    if traced:
        with span("bench.trace_reduce"):
            summary = xplane.reduce_file(xplane.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for name, sec in summary["breakdown"]["device_ops"]:
            log(f"[trace] {sec:.6f} s in {summary['op_counts'][name]} x {name}: "
                f"{summary['op_text'][name][:300]}")
        log(f"[trace] busy {summary['busy_s']:.3f} s of {summary['window_s']:.3f} s; "
            f"idle gaps {summary['breakdown']['idle_gaps']}")

    dev = dict(device, memory_peak_bytes=peak)
    if traced:
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])

    metrics: Dict[str, dict] = {}
    breakdown = None
    if not traced:
        e2e = window_metrics(records, window_s) if ok else {}
        e2e["setup_s"] = setup_s
        lat = sorted(r["done"] - r["submit"] for r in ok)
        log(f"[window] latency over {len(lat)} answers: {[round(x, 3) for x in lat]}")
        for m in metrics_for(bench, cell, "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        record = {
            "cell": cell, "config": cfg, "records": records, "pumps": pumps,
            "window_s": window_s, "trace": summary, "graph": {
                "n": g.n, "m": g.m,
                "sources": int(np.count_nonzero(np.bincount(
                    np.asarray(g.src), minlength=g.n)))},
            "peak": _peak_of(data_dir, device["kind"]),
        }
        for m in metrics_for(bench, cell, "per_layer"):
            value = load_reader(data_dir, m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        breakdown = summary["breakdown"]

    src, dst, labels = driver.src_host, driver.dst_host, np.asarray(g.labels)
    driver.close()
    del driver, g
    t_ref = time.perf_counter()
    with span("bench.reference"):
        chk = check_answers(records, src, dst, labels)
    log(f"[check] reference over {chk['checked']} answers: {time.perf_counter() - t_ref:.3f} s"
        + ("" if chk["checked"] else "; no answer to check, so not correct"))
    limits = {"wrong_vertices": 0, "wrong_arcs": 0, "unanswered": 0}
    values = {"wrong_vertices": chk["wrong_vertices"], "wrong_arcs": chk["wrong_arcs"],
              "unanswered": failed}
    correct = bool(ok) and chk["checked"] > 0 and all(
        values[k] <= limits[k] for k in limits)
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    return out


def _warm(driver, queries, clients: int) -> None:
    """Run the shapes the window will run, so that it compiles nothing: one
    query of each shape for a driver that answers one at a time; one full
    batch of the first `clients` queries for a batching engine (whose
    programs are shaped by the batch and its mix of shapes)."""
    if hasattr(driver, "answer"):
        first = {}
        for q in queries:
            first.setdefault(q.shape, q)
        for q in first.values():
            t0 = time.perf_counter()
            driver.answer(q)
            log(f"[set-up] warm-up {q.shape} {list(q.labels)}: {time.perf_counter() - t0:.3f} s")
        return
    for j, q in enumerate(queries[:clients]):
        driver.submit(q, -1 - j)
    left = clients
    while left:
        left -= len(driver.pump(force=True))


def _peak_of(data_dir: str, kind: str) -> Optional[dict]:
    try:
        return work.load_peak(data_dir, kind)
    except KeyError:
        return None


def _memory_peak() -> int:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class _CompileCounter:
    """Counts, while open, programs compiled (`n`) and programs loaded from
    the persistent compilation cache (`loaded`) instead of compiled."""

    def __init__(self):
        self.requests = self.loaded = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def n(self) -> int:
        return self.requests - self.loaded

    def _duration(self, event: str, duration: float, **kw) -> None:
        if self._on and event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event: str, **kw) -> None:
        if self._on and event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
        return False
