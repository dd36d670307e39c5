"""The `nlcc.wave_arc_share` reader on a synthetic span buffer, each case in
which it reads nothing, and on the spans of real queries answered by the
local driver at a tiny size on the CPU. The buffer is the one the other
program-span readers are tested on, with one `nlcc.wave_loop` span a query."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

import json
import os
import sys

import pytest

import drivers
import harness
from benchtest import BENCH, LOCAL, TINY_GRAPH
from repro import obs
from test_program_metrics import _query, _records, _span, read, ring  # noqa: F401
from traffic import load_workload, make_queries

NAME = "nlcc.wave_arc_share"


def _waves(root, t0, arcs=64, hops=3, waves=5, m=1000):
    """One prune's spans with one constraint's wave loop, whose hops step
    over `arcs` of the graph's `m` arcs, `hops` hops a wave, `waves` waves."""
    loop = _span("nlcc.wave_loop", root + 50, root, root, t0 + 6, t0 + 7, {},
                 {"wave_arcs": arcs * hops * waves, "graph_arcs": m * hops * waves})
    return _query(root, t0, m=m) + [loop]


def _window():
    return _records((19.5, 30.5), (39.5, 50.5))


def test_share_on_a_synthetic_buffer(ring):
    warm = _waves(1, 0.0, arcs=1, waves=99)  # warm-up: outside the window
    ring(warm + _waves(100, 20.0) + _waves(200, 40.0, arcs=1000, hops=4, waves=2))
    # query 100's hops step over 64 of 1,000 arcs, query 200's over all
    assert read(NAME, _window()) == pytest.approx(
        100.0 * (64 * 15 + 1000 * 8) / (1000 * 15 + 1000 * 8))
    ring(_waves(100, 20.0, arcs=256, waves=7))
    assert read(NAME, _records((19.5, 30.5))) == pytest.approx(25.6)


@pytest.mark.parametrize("spans", ["no_loop", "no_counters", "no_waves"])
def test_share_reads_nothing_without_wave_arcs(ring, spans):
    """A program whose wave loops count no arcs (the parent of the
    compaction), or a window in which no wave ran, has no share."""
    plain = _query(100, 20.0)
    ring({"no_loop": plain,
          "no_counters": plain + [_span("nlcc.wave_loop", 150, 100, 100, 26.0, 27.0)],
          "no_waves": _waves(100, 20.0, waves=0)}[spans])
    assert read(NAME, _records((19.5, 30.5))) is None
    assert read("prune.readback_mb_per_query", _records((19.5, 30.5))) == pytest.approx(2.0)


def test_share_reads_nothing_without_one_root_per_answered_query(ring):
    ring(_waves(100, 20.0) + _waves(200, 40.0))
    assert read(NAME, _records((19.5, 30.5), status="error")) is None
    assert read(NAME, {"records": []}) is None
    assert read(NAME, _records((19.5, 30.5), (39.5, 50.5), (60.0, 61.0))) is None
    assert read(NAME, _records((19.5, 50.5))) is None
    assert read(NAME, _window()) == pytest.approx(6.4)


@pytest.mark.parametrize("room", [0, 1])
def test_share_reads_nothing_where_the_ring_dropped_a_window_span(ring, room):
    """A full ring pushes out query 100's `prune.plan` and the window reads
    nothing; with one place to spare nothing is dropped and it reads."""
    spans = _waves(100, 20.0) + _waves(200, 40.0)
    ring(spans, maxlen=len(spans) + room)
    with obs.span("after"):
        pass
    assert obs.dropped() == 1 - room
    assert (read(NAME, _window()) is None) == (room == 0)


def test_share_reads_nothing_from_a_program_without_the_recorder(ring, monkeypatch):
    ring(_waves(100, 20.0))
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import raises ImportError
    assert read(NAME, _records((19.5, 30.5))) is None


def test_share_on_queries_answered_by_the_local_driver():
    """Real spans: two triangle queries through `LocalBlocked` at a tiny size."""
    cfg = json.load(open(os.path.join(BENCH, "configs", f"{LOCAL[0]}.json")))
    cfg["graph"].update(TINY_GRAPH)
    driver = drivers.DRIVERS[cfg["engine"]](cfg, 2**31 + 99, BENCH, {})
    try:
        wl = load_workload(BENCH, LOCAL[1])
        wl["rare_max_vertices"] = 130
        g = driver.graph
        queries = [q for q in make_queries(wl, 2**31 + 99, g.label_freq, g.needle_labels)
                   if q.shape == "cycle3"][:1]
        driver.answer(queries[0])  # warm-up, outside the window
        records = []
        for _ in range(2):
            harness.run_closed_loop_single(driver, queries, 0.0, records)
    finally:
        driver.close()
    assert [r["status"] for r in records] == ["ok", "ok"]
    assert 0 < read(NAME, {"records": records}) <= 100
