"""The program-span readers (`prune.host_s_per_query`,
`prune.readback_mb_per_query`, `prune.trace_s_per_query`,
`nlcc.edge_prune_s_per_query`, `lcc.active_arc_share`) on a synthetic span
buffer, each case in which they read nothing, and on the spans of real
queries answered by the local driver at a tiny size on the CPU."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

import collections
import json
import os
import sys

import numpy as np
import pytest

import drivers
import harness
from benchtest import BENCH, LOCAL, TINY_GRAPH
from repro import obs
from traffic import load_workload, make_queries

NAMES = ("prune.host_s_per_query", "prune.readback_mb_per_query",
         "prune.trace_s_per_query", "nlcc.edge_prune_s_per_query",
         "lcc.active_arc_share")


def read(name, record):
    return harness.load_reader(BENCH, name)(record)


def _span(name, sid, parent, query, t0, t1, attrs=None, counters=None):
    return obs.Span(name, sid, parent, query, t0, t1, attrs or {}, counters or {})


def _query(root, t0, host=(0.5, 0.25), read_bytes=2_000_000, trace=0.125,
           edge=1.5, sweeps=(4, 6), active=(3000, 1000), m=1000):
    """The spans of one prune whose root has id `root`, from t0 to t0 + 10."""
    q, out = root, []
    sid = root + 1
    out.append(_span("prune.plan", sid, q, q, t0 + 0.1, t0 + 0.1 + host[0],
                     {"kind": "host"}))
    sid += 1
    # a host span inside another host span counts once, with its outer one,
    # and a read inside a host span is not host work
    out.append(_span("nlcc.sources", sid, sid - 1, q, t0 + 0.2, t0 + 0.3, {"kind": "host"}))
    sid += 1
    out.append(_span("host.readback", sid, sid - 1, q, t0 + 0.2, t0 + 0.225, {"what": "x"}))
    sid += 1
    out.append(_span("nlcc.edge_prune", sid, q, q, t0 + 1, t0 + 1 + edge, {},
                     {"trace_s": trace}))
    edge_id = sid
    sid += 1
    out.append(_span("nlcc.edge_prune.support", sid, edge_id, q, t0 + 1.1,
                     t0 + 1.1 + host[1], {"kind": "host"}))
    sid += 1
    out.append(_span("host.readback", sid, edge_id, q, t0 + 2, t0 + 2.1, {"what": "src"},
                     {"readback_bytes": read_bytes}))
    sid += 1
    for k, (s, a) in enumerate(zip(sweeps, active)):
        out.append(_span("lcc.fixpoint", sid, q, q, t0 + 3 + k, t0 + 3.5 + k, {},
                         {"stepped_arcs": s * m, "active_arcs": a}))
        sid += 1
    out.append(_span("prune", root, None, root, t0, t0 + 10, {"n0": 3, "constraints": 1}))
    return out


@pytest.fixture
def ring(monkeypatch):
    """A fresh ring holding the given spans."""
    def fill(spans, maxlen=obs.RING):
        buf = collections.deque(spans, maxlen=maxlen)
        monkeypatch.setattr(obs, "_ring", buf)
        monkeypatch.setattr(obs, "_dropped", 0)
        monkeypatch.setattr(obs, "_dropped_t1", float("-inf"))
        return buf
    return fill


def _records(*windows, status="ok"):
    return {"records": [{"status": status, "submit": a, "done": b} for a, b in windows]}


def test_readers_on_a_synthetic_buffer(ring):
    warm = _query(1, 0.0, read_bytes=9e9, trace=99.0)  # warm-up: outside the window
    ring(warm + _query(100, 20.0) + _query(200, 40.0, sweeps=(2,), active=(500,)))
    rec = _records((19.5, 30.5), (39.5, 50.5))
    assert read("prune.host_s_per_query", rec) == pytest.approx(0.725)
    assert read("prune.readback_mb_per_query", rec) == pytest.approx(2.0)
    assert read("prune.trace_s_per_query", rec) == pytest.approx(0.125)
    assert read("nlcc.edge_prune_s_per_query", rec) == pytest.approx(1.5)
    assert read("lcc.active_arc_share", rec) == pytest.approx(
        100.0 * (3000 + 1000 + 500) / ((4 + 6 + 2) * 1000))


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_nothing_without_one_root_per_answered_query(ring, name):
    ring(_query(100, 20.0) + _query(200, 40.0))
    # no query answered
    assert read(name, _records((19.5, 30.5), status="error")) is None
    assert read(name, {"records": []}) is None
    # an answered query with no prune span inside it
    assert read(name, _records((19.5, 30.5), (39.5, 50.5), (60.0, 61.0))) is None
    # two roots inside one answered query
    assert read(name, _records((19.5, 50.5))) is None
    # the same window reads where roots and answers pair up
    assert read(name, _records((19.5, 30.5), (39.5, 50.5))) is not None


def _fill_then_push(ring, room):
    """The window's spans in a ring with `room` free places, then a span
    outside every query: in a full ring it pushes out the window's first
    span, query 100's `prune.plan`, and both roots stay."""
    spans = _query(100, 20.0) + _query(200, 40.0)
    ring(spans, maxlen=len(spans) + room)
    with obs.span("after"):
        pass
    assert sum(s.name == "prune" for s in obs.spans()) == 2


def _read_window(name):
    return read(name, _records((19.5, 30.5), (39.5, 50.5)))


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_nothing_where_the_ring_dropped_a_window_span(ring, name):
    _fill_then_push(ring, room=0)
    assert obs.dropped() == 1
    assert obs.spans()[0].id == 102  # query 100's `prune.plan` (101) is gone
    assert _read_window(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_the_same_window_where_the_ring_dropped_nothing(ring, name):
    _fill_then_push(ring, room=1)
    assert obs.dropped() == 0
    assert _read_window(name) is not None


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_nothing_from_a_program_without_the_recorder(
        ring, monkeypatch, name):
    ring(_query(100, 20.0))
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import raises ImportError
    assert read(name, _records((19.5, 30.5))) is None


def test_active_share_reads_nothing_without_a_sweep(ring):
    spans = [s for s in _query(100, 20.0) if s.name != "lcc.fixpoint"]
    ring(spans)
    assert read("lcc.active_arc_share", _records((19.5, 30.5))) is None
    assert read("prune.readback_mb_per_query", _records((19.5, 30.5))) == pytest.approx(2.0)


def test_readers_on_queries_answered_by_the_local_driver(tmp_path):
    """Real spans: two queries through `LocalBlocked` at a tiny size."""
    cfg = json.load(open(os.path.join(BENCH, "configs", f"{LOCAL[0]}.json")))
    cfg["graph"].update(TINY_GRAPH)
    driver = drivers.DRIVERS[cfg["engine"]](cfg, 2**31 + 99, BENCH, {})
    try:
        g = driver.graph
        wl = load_workload(BENCH, LOCAL[1])
        wl["rare_max_vertices"] = 130
        queries = [q for q in make_queries(wl, 2**31 + 99, g.label_freq, g.needle_labels)
                   if q.shape == "cycle3"][:1]
        driver.answer(queries[0])  # warm-up, outside the window
        records = []
        for _ in range(2):  # one query each
            harness.run_closed_loop_single(driver, queries, 0.0, records)
    finally:
        driver.close()
    rec = {"records": records}
    assert [r["status"] for r in records] == ["ok", "ok"]
    got = {name: read(name, rec) for name in NAMES}
    assert all(v is not None for v in got.values()), got
    assert got["prune.readback_mb_per_query"] > 0
    assert got["prune.host_s_per_query"] > 0
    assert 0 < got["lcc.active_arc_share"] <= 100
    nlcc = read("nlcc.s_per_query", rec)
    assert 0 < got["nlcc.edge_prune_s_per_query"] <= nlcc
    assert np.isfinite(got["prune.trace_s_per_query"])
