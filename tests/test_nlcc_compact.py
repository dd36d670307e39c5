"""NLCC waves on the active subgraph (`core/nlcc.py`): where a constraint's
waves run as XLA programs they step over the active arcs and the vertices
those arcs touch, compacted once per constraint; survivors and omega are
bit-identical to the same waves over the whole graph."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.core import Template, init_state, nlcc, prune
from repro.core.state import PruneState
from repro.core.template import NonLocalConstraint
from repro.graph import generators as gen
from repro.graph.blocked import build_blocked_structure
from repro.graph.structs import DeviceGraph
from repro.kernels import registry

TRIANGLE = Template([0, 1, 2], [(0, 1), (1, 2), (2, 0)])
CONSTRAINTS = {
    "cycle": NonLocalConstraint("cycle", (0, 1, 2, 0)),
    "path": NonLocalConstraint("path", (0, 1, 2)),
}


@pytest.fixture(autouse=True)
def _small_buckets(monkeypatch):
    """Buckets small enough that a test graph's active subgraph is under m,
    and no capacity carried over from another test."""
    monkeypatch.setattr(nlcc, "COMPACT_MIN", 64)
    monkeypatch.setattr(nlcc, "_compact_buckets", {})
    obs.reset()
    yield
    obs.reset()
    registry.set_policy(None)


def _graph(n=600, seed=11, bn=64):
    g = gen.erdos_renyi_graph(n, 30.0, seed=seed, n_labels=3)
    dg = DeviceGraph.from_host(g)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=bn)
    return g, dg, bs


def _state(dg, share, seed=5):
    """Every vertex a candidate of its label; active arcs are those inside a
    random vertex set holding sqrt(share) of the vertices: about `share` of
    the arcs, closing triangles among themselves. Vertex 0, where a pad
    source's id is clipped to, is in the set where any vertex is."""
    st = init_state(dg, TRIANGLE)
    inside = np.random.default_rng(seed).random(dg.n) < np.sqrt(share)
    inside[0] = share > 0
    ea = inside[np.asarray(dg.src)] & inside[np.asarray(dg.dst)]
    return PruneState(omega=st.omega, edge_active=jnp.asarray(ea))


def _fused_policy():
    pol = registry.DispatchPolicy()
    pol.set_route(nlcc.NLCC_ROUTE, "cpu", registry.BUCKET_ANY, registry.ROUTE_FUSED)
    registry.set_policy(pol)
    return pol


def _whole_graph(monkeypatch):
    monkeypatch.setattr(nlcc, "compact_bucket", lambda *a: None)


def _wave_loops():
    return [s for s in obs.spans() if s.name == "nlcc.wave_loop"]


def _run(dg, st, c, blocked, monkeypatch, *, whole, **kw):
    with monkeypatch.context() as mp:
        if whole:
            _whole_graph(mp)
        stats = {}
        out = nlcc.verify_constraint(dg, st, c, TRIANGLE.labels, wave=32,
                                     stats=stats, blocked=blocked, **kw)
    return out, stats


@pytest.mark.parametrize("share", [0.0, 0.04, 1.0], ids=["none", "few", "all"])
@pytest.mark.parametrize("kind", ["cycle", "path"])
@pytest.mark.parametrize("route", ["unpacked", "fused"])
def test_compacted_waves_match_the_whole_graph(monkeypatch, route, kind, share):
    g, dg, bs = _graph()
    st = _state(dg, share)
    blocked = None
    if route == "fused":
        _fused_policy()
        blocked = bs
    c = CONSTRAINTS[kind]
    want, want_stats = _run(dg, st, c, blocked, monkeypatch, whole=True)
    obs.reset()
    got, got_stats = _run(dg, st, c, blocked, monkeypatch, whole=False)
    np.testing.assert_array_equal(np.asarray(got.omega), np.asarray(want.omega))
    np.testing.assert_array_equal(np.asarray(got.edge_active), np.asarray(st.edge_active))
    assert got_stats == want_stats
    assert got_stats["nlcc_host_syncs"] == 1
    (loop,) = _wave_loops()
    hops = len(c.walk) - 1
    assert loop.counters["graph_arcs"] == dg.m * hops * got_stats["nlcc_waves"] > 0
    if share == 1.0:  # every arc active: the bucket is not under m
        assert loop.counters["wave_arcs"] == loop.counters["graph_arcs"]
    else:
        assert loop.counters["wave_arcs"] < loop.counters["graph_arcs"] / 4
    if share == 0.04:  # some heads survive and some do not
        heads = np.asarray(st.omega[:, c.walk[0]])
        kept = np.asarray(got.omega[:, c.walk[0]])
        assert 0 < kept.sum() < heads.sum()


@pytest.mark.parametrize("kind", ["cycle", "path"])
def test_sources_without_active_arcs_and_pad_sources(kind):
    """A wave of real sources, sources that no active arc touches, and pads:
    on the compact graph the last two map to -1 and fail, as they fail on
    the whole graph."""
    g, dg, bs = _graph()
    st = _state(dg, 0.04)
    c = CONSTRAINTS[kind]
    touched, extent = nlcc._active_extent(dg.src, dg.dst, st.edge_active, dg.n)
    touched = np.asarray(touched)
    heads = np.asarray(st.omega[:, c.walk[0]])
    live = np.flatnonzero(heads & touched)[:20]
    dead = np.flatnonzero(heads & ~touched)[:8]
    assert live.size == 20 and dead.size == 8
    ids = np.full(32, -1, np.int32)
    ids[:28] = np.concatenate([live, dead])
    ids = jnp.asarray(ids)
    m_c, n_c = nlcc.compact_bucket(dg.n, dg.m, *map(int, np.asarray(extent)))
    cg, cst, to_c = nlcc.compact_active(dg, st, jnp.asarray(touched), m_c=m_c, n_c=n_c)
    ids_c = np.asarray(nlcc._compact_ids(to_c, ids))
    assert (ids_c[:20] >= 0).all() and (ids_c[20:] == -1).all()
    # the compact dst stays sorted, pads end at the sink
    dst_c = np.asarray(cg.dst)
    assert (np.diff(dst_c) >= 0).all() and dst_c[-1] == n_c - 1
    walk = c.walk
    cand = jnp.stack([st.omega[:, q] for q in walk])
    cand_c = jnp.stack([cst.omega[:, q] for q in walk])
    want, want_msgs = nlcc.check_walk_constraint(
        dg, st, cand, c.is_cyclic, ids, count_messages=True)
    got, got_msgs = nlcc.check_walk_constraint(
        cg, cst, cand_c, c.is_cyclic, jnp.asarray(ids_c), count_messages=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got_msgs) == int(want_msgs) > 0
    assert np.asarray(want)[:20].any() and not np.asarray(want)[20:].any()


@pytest.mark.parametrize("count_messages", [False, True], ids=["plain", "messages"])
def test_prune_with_edge_prune_is_bit_identical(monkeypatch, count_messages):
    """The whole pipeline, with the edge-prune pass removing arcs before the
    waves, and under `count_messages` (the boolean-plane route): the same
    omega, arcs and counts with and without the compaction."""
    g, dg, bs = _graph(n=900, seed=4)
    kw = dict(wave=32, nlcc_edge_prune=True, collect_stats=count_messages,
              label_freq=g.label_frequency())

    def run(whole):
        with monkeypatch.context() as mp:
            if whole:
                _whole_graph(mp)
            obs.reset()
            res = prune(dg, TRIANGLE, **kw)
        extra = {}
        for p in res.phases:
            for k, v in p.extra.items():
                if isinstance(v, (int, float)):
                    extra[k] = extra.get(k, 0) + v
        return res, extra, _wave_loops()

    want, want_x, _ = run(True)
    got, got_x, loops = run(False)
    np.testing.assert_array_equal(np.asarray(got.state.omega), np.asarray(want.state.omega))
    np.testing.assert_array_equal(np.asarray(got.state.edge_active),
                                  np.asarray(want.state.edge_active))
    assert got_x == want_x
    assert got_x["nlcc_edges_pruned"] > 0
    if count_messages:
        assert got_x["nlcc_messages"] == want_x["nlcc_messages"] > 0
    assert got_x["nlcc_host_syncs"] == (2 if count_messages else 1) * got_x["nlcc_constraints"]
    assert sum(s.counters["wave_arcs"] for s in loops) < sum(
        s.counters["graph_arcs"] for s in loops)


@pytest.mark.parametrize("route", ["packed", "fused_kernel"])
def test_kernel_routes_keep_the_whole_graph(monkeypatch, route):
    """The `bitset_spmm` per-hop route and the `bitset_wave` kernel walk the
    whole graph's blocked structure: no compaction, and the same survivors
    as the boolean planes."""
    g, dg, bs = _graph(n=300)
    st = _state(dg, 0.04)
    c = CONSTRAINTS["cycle"]
    calls = []
    real = nlcc.compact_active
    monkeypatch.setattr(nlcc, "compact_active", lambda *a, **k: calls.append(1) or real(*a, **k))
    want, _ = _run(dg, st, c, None, monkeypatch, whole=True)
    obs.reset()
    if route == "packed":
        got, stats = _run(dg, st, c, bs, monkeypatch, whole=False, force_pallas=True)
        assert stats["nlcc_packed_waves"] > 0
    else:
        pol = _fused_policy()
        pol.set_mode("bitset_wave", "cpu", registry.BUCKET_ANY, registry.MODE_INTERPRET)
        with registry.count_dispatches() as counts:
            got, stats = _run(dg, st, c, bs, monkeypatch, whole=False)
        assert stats["nlcc_fused_waves"] > 0
        assert counts[("bitset_wave", registry.MODE_INTERPRET)] == stats["nlcc_fused_waves"]
    np.testing.assert_array_equal(np.asarray(got.omega), np.asarray(want.omega))
    assert calls == []
    (loop,) = _wave_loops()
    assert loop.counters["wave_arcs"] == loop.counters["graph_arcs"] > 0


def test_constraints_of_one_bucket_compile_the_compaction_once(monkeypatch):
    g, dg, bs = _graph(n=433, seed=7)  # a shape no other test compiles
    st = _state(dg, 0.04)
    before = nlcc.compact_active._cache_size()
    for kind in ("cycle", "path"):
        nlcc.verify_constraint(dg, st, CONSTRAINTS[kind], TRIANGLE.labels, wave=32)
    assert len(nlcc._compact_buckets[(dg.n, dg.m)]) == 1
    assert nlcc.compact_active._cache_size() == before + 1
    # a smaller active subgraph runs at the capacity already built
    nlcc.verify_constraint(dg, _state(dg, 0.01), CONSTRAINTS["cycle"], TRIANGLE.labels,
                           wave=32)
    assert nlcc.compact_active._cache_size() == before + 1


def test_compact_bucket_reuses_the_smallest_capacity_that_holds():
    assert nlcc.compact_bucket(5000, 100_000, 10, 10) == (64, 64)
    assert nlcc.compact_bucket(5000, 100_000, 3000, 200) == (4096, 256)
    # a built capacity that holds the subgraph is taken, the smallest first
    assert nlcc.compact_bucket(5000, 100_000, 50, 63) == (64, 64)
    assert nlcc.compact_bucket(5000, 100_000, 100, 63) == (4096, 256)
    # the sink needs a vertex of its own
    assert nlcc.compact_bucket(5000, 100_000, 10, 64) == (4096, 256)
    # a bucket that is not under m: the whole graph
    assert nlcc.compact_bucket(5000, 100_000, 70_000, 10) is None
    assert nlcc.compact_bucket(5000, 100_000, 0, 0) == (64, 64)
    # another graph shape has its own capacities
    assert nlcc.compact_bucket(5000, 100_001, 50, 10) == (64, 64)
