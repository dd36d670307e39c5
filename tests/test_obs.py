"""The span recorder (`repro.obs`): nesting, counters, the ring's bound,
readback bytes, JAX's tracing time, the profiler's clock, and the spans of
one small blocked prune."""
import collections
import glob

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import Template, prune
from repro.graph import generators as gen
from repro.graph.blocked import build_blocked_structure
from repro.graph.structs import DeviceGraph


@pytest.fixture(autouse=True)
def _empty_ring():
    obs.reset()
    yield
    obs.reset()


def test_spans_nest_under_one_query():
    with obs.span("prune", n0=3) as root:
        with obs.span("a") as a:
            with obs.span("b", kind="host") as b:
                pass
        with obs.span("c") as c:
            pass
    with obs.span("prune") as other:
        pass
    got = {s.id: s for s in obs.spans()}
    assert [s.name for s in obs.spans()] == ["b", "a", "c", "prune", "prune"]
    assert got[root.id].parent is None and got[root.id].attrs == {"n0": 3}
    assert got[a.id].parent == root.id and got[c.id].parent == root.id
    assert got[b.id].parent == a.id and got[b.id].attrs == {"kind": "host"}
    assert {got[i].query for i in (root.id, a.id, b.id, c.id)} == {root.id}
    assert got[other.id].query == other.id
    assert root.t0 <= a.t0 <= b.t0 <= b.t1 <= a.t1 <= c.t0 <= c.t1 <= root.t1
    assert root.seconds == pytest.approx(root.t1 - root.t0)


def test_count_lands_on_the_innermost_open_span():
    obs.count("lost")  # no span open: nothing to add to
    with obs.span("outer") as outer:
        obs.count("n", 2)
        with obs.span("inner") as inner:
            obs.count("n")
            obs.count("n", 4)
        obs.count("m", 0.5)
    assert inner.counters == {"n": 5}
    assert outer.counters == {"n": 2, "m": 0.5}
    assert all("lost" not in s.counters for s in obs.spans())


def test_a_span_that_raises_is_recorded_and_closed():
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("fails"):
                raise ValueError("x")
    with obs.span("after") as after:
        pass
    assert [s.name for s in obs.spans()] == ["fails", "outer", "after"]
    assert after.parent is None


def test_ring_is_bounded_and_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=4))
    recs = []
    for i in range(6):
        with obs.span(f"s{i}") as r:
            recs.append(r)
    assert [s.name for s in obs.spans()] == ["s2", "s3", "s4", "s5"]
    assert obs.dropped() == 2
    assert not obs.intact_since(recs[1].t1)
    assert obs.intact_since(recs[2].t0)
    obs.reset()
    assert obs.dropped() == 0 and obs.intact_since(recs[0].t0)


def test_to_host_counts_bytes_of_device_arrays_only():
    with obs.span("q") as q:
        h = obs.to_host(np.ones((3, 5), np.int32), "numpy")
        assert isinstance(h, np.ndarray) and h.shape == (3, 5)
        d = obs.to_host(jnp.ones((4, 8), jnp.int32), "device")
        np.testing.assert_array_equal(d, np.ones((4, 8), np.int32))
        obs.to_host(jnp.zeros(10, bool), "flags")
    reads = [s for s in obs.spans() if s.name == "host.readback"]
    assert [s.attrs["what"] for s in reads] == ["device", "flags"]
    assert [s.parent for s in reads] == [q.id, q.id]
    assert [s.counters for s in reads] == [{"readback_bytes": 128}, {"readback_bytes": 10}]
    assert "readback_bytes" not in q.counters


def test_to_host_moves_a_tuple_in_one_read():
    with obs.span("q"):
        a, b, c = obs.to_host((jnp.ones((4, 2), bool), jnp.arange(3, dtype=jnp.int32),
                               np.arange(5)), "pair")
        d, = obs.to_host((np.zeros(2),), "numpy")
    np.testing.assert_array_equal(a, np.ones((4, 2), bool))
    np.testing.assert_array_equal(b, np.arange(3))
    np.testing.assert_array_equal(c, np.arange(5))
    assert isinstance(d, np.ndarray)
    reads = [s for s in obs.spans() if s.name == "host.readback"]
    assert [s.attrs["what"] for s in reads] == ["pair"]
    assert reads[0].counters == {"readback_bytes": 8 + 12}


def test_a_forced_retrace_lands_in_trace_s():
    f = jax.jit(lambda x: jnp.sin(x) * 3 + 1)
    f(jnp.ones(3)).block_until_ready()
    with obs.span("warm") as warm:
        f(jnp.ones(3)).block_until_ready()
    with obs.span("retrace") as retrace:
        f(jnp.ones(17)).block_until_ready()  # a new shape: traced, lowered, compiled
    assert warm.counters.get("trace_s", 0.0) == 0.0
    assert retrace.counters["trace_s"] > 0
    assert retrace.counters["trace_s"] <= retrace.seconds


def _small_blocked(seed=3, n=200):
    g = gen.erdos_renyi_graph(n, 5.0, seed=seed, n_labels=4)
    dg = DeviceGraph.from_host(g)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=64)
    return g, dg, bs


TRIANGLE = Template([0, 1, 2], [(0, 1), (1, 2), (2, 0)])


def _prune(g, dg, bs):
    return prune(dg, TRIANGLE, blocked=bs, wave=32, nlcc_edge_prune=True,
                 label_freq=g.label_frequency())


def test_small_blocked_prune_spans_match_its_stats():
    g, dg, bs = _small_blocked()
    res = _prune(g, dg, bs)
    spans = obs.spans()
    roots = [s for s in spans if s.name == "prune"]
    assert len(roots) == 1 and roots[0].parent is None
    assert roots[0].attrs == {"n0": 3, "constraints": res.stats["n_constraints"]}
    assert all(s.query == roots[0].id for s in spans)
    phases = [s for s in spans if s.name == "prune.phase"]
    assert [s.attrs["phase"] for s in phases] == [p.phase for p in res.phases]
    assert [s.seconds for s in phases] == [p.seconds for p in res.phases]
    nlcc_phases = [s for s in phases if s.attrs["phase"] != "LCC"]
    assert [p["actual_s"] for p in res.stats["plan"]["phases"]] == [
        s.seconds for s in nlcc_phases]
    fences = [s for s in spans if s.name == "prune.fence"]
    assert sorted(s.parent for s in fences) == sorted(s.id for s in phases)
    fix = [s for s in spans if s.name == "lcc.fixpoint"]
    assert sum(s.counters["stepped_arcs"] for s in fix) == res.stats["lcc_iterations"] * dg.m
    assert all(0 < s.counters["active_arcs"] <= s.counters["stepped_arcs"] for s in fix)
    waves = [s for s in spans if s.name == "nlcc.wave"]
    assert len(waves) == sum(p.extra.get("nlcc_waves", 0) for p in res.phases) > 0
    edge = [s for s in spans if s.name == "nlcc.edge_prune"]
    assert len(edge) == res.stats["n_constraints"]
    # each edge-prune wave reads back its two live planes inside the pass
    for e in edge:
        planes = [s.attrs["what"] for s in spans
                  if s.parent == e.id and s.name == "host.readback"]
        assert planes.count("edge_prune.fwd_live") == planes.count("edge_prune.rev_live") >= 1
    support = [s for s in spans if s.name == "nlcc.edge_prune.support"]
    assert support and all(s.attrs == {"kind": "host"} for s in support)
    assert sum(s.counters.get("readback_bytes", 0) for s in spans) > 0
    # the edge-prune pass reads outside its host spans, and a host span
    # holds no fence
    host = {s.id for s in spans if s.attrs.get("kind") == "host"}
    assert not [s for s in spans if s.parent in host]


def test_prune_is_bit_identical_with_the_profiler_on(tmp_path):
    g, dg, bs = _small_blocked(seed=5)
    off = _prune(g, dg, bs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.prune"):
            on = _prune(g, dg, bs)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(np.asarray(off.state.omega), np.asarray(on.state.omega))
    np.testing.assert_array_equal(np.asarray(off.state.edge_active),
                                  np.asarray(on.state.edge_active))
    assert [p.phase for p in off.phases] == [p.phase for p in on.phases]
    assert off.stats["lcc_iterations"] == on.stats["lcc_iterations"]

    # the spans are on the trace's host plane, inside the enclosing span
    from jax.profiler import ProfileData

    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    bench = [ev for ev in events if ev.name == "bench.prune"]
    assert len(bench) == 1
    b0, b1 = bench[0].start_ns, bench[0].start_ns + bench[0].duration_ns
    for name in ("prune", "lcc.fixpoint", "host.readback"):
        got = [ev for ev in events if ev.name == name]
        assert got, name
        assert all(b0 <= ev.start_ns and ev.start_ns + ev.duration_ns <= b1 for ev in got)
    root = [ev for ev in events if ev.name == "prune"][0]
    rec = [s for s in obs.spans() if s.name == "prune"][-1]
    assert root.duration_ns / 1e9 == pytest.approx(rec.seconds, rel=0.05, abs=2e-3)
