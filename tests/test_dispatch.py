"""Compat/registry dispatch contract (CPU-runnable):

  - routing: force_pallas off-TPU -> interpret mode, ineligible shapes -> ref,
    plain CPU calls -> ref, for all four registered kernels,
  - parity: the interpret-mode Pallas path and the reference oracle agree
    (allclose / exact) through the SAME public ops wrapper,
  - no hidden fallback: a Pallas entrypoint that fails raises; an ineligible
    shape routes to the oracle by decision and shows in the dispatch counts,
  - compat: make_mesh accepts axis-type names, shard_map and the TPU
    compiler params take this JAX's one spelling; packed NLCC frontier
    equals the boolean-plane wave.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.graph.blocked import build_blocked_structure
from repro.graph.structs import DeviceGraph
from repro.graph import generators as gen
from repro.kernels import compat, ops, ref, registry


def _graph_args(scale=6, w=2, bn=64):
    g = gen.rmat_graph(scale, edge_factor=4, seed=scale)
    dg = DeviceGraph.from_host(g)
    rng = np.random.default_rng(scale)
    vals = jnp.asarray(rng.integers(0, 2**32, size=(g.n, w), dtype=np.uint32))
    active = jnp.asarray(rng.random(dg.m) < 0.7)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=bn)
    return (vals, dg.src, dg.dst, g.n, active, bs)


def _attn_args(s=256, d=128):
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.standard_normal((1, 2, s, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, s, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, s, d)) * 0.3, jnp.float32)
    return (q, k, v)


def _seg_args(nt=8, dd=5, f=128):
    rng = np.random.default_rng(nt + f)
    feats = jnp.asarray(rng.standard_normal((nt, dd, f)), jnp.float32)
    mask = jnp.asarray(rng.random((nt, dd)) < 0.8)
    return (feats, mask)


def _bag_args(v=200, d=128, b=4, l=3):
    rng = np.random.default_rng(v)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, size=(b, l)), jnp.int32)
    weights = jnp.asarray((rng.random((b, l)) < 0.9), jnp.float32)
    return (table, ids, weights)


def test_all_five_kernels_registered():
    assert registry.names() == (
        "bitset_spmm", "bitset_wave", "embedding_bag", "flash_attention",
        "segment_agg",
    )


def _wave_args(scale=6, w=2, bn=64, hops=3):
    vals, src, dst, n, active, bs = _graph_args(scale=scale, w=w, bn=bn)
    rng = np.random.default_rng(scale + hops)
    cand = jnp.asarray(
        np.where(rng.random((hops, n)) < 0.8, np.uint32(0xFFFFFFFF), np.uint32(0))
    )
    return (vals, src, dst, n, active, cand, bs)


# --------------------------------------------------------------- routing
CASES = [
    ("bitset_spmm", _graph_args(), {}),
    ("bitset_wave", _wave_args(), {}),
    ("segment_agg", _seg_args(), {}),
    ("flash_attention", _attn_args(), {"causal": True, "window": None,
                                       "block_q": 128, "block_k": 128}),
    ("embedding_bag", _bag_args(), {"mode": "sum"}),
]


@pytest.mark.parametrize("name,args,kw", CASES, ids=[c[0] for c in CASES])
def test_force_pallas_routes_to_interpret_off_tpu(name, args, kw):
    assert registry.resolve_mode(
        name, *args, force_pallas=True, backend="cpu", **kw
    ) == registry.MODE_INTERPRET


@pytest.mark.parametrize("name,args,kw", CASES, ids=[c[0] for c in CASES])
def test_cpu_without_force_routes_to_ref(name, args, kw):
    assert registry.resolve_mode(
        name, *args, backend="cpu", **kw
    ) == registry.MODE_REF


@pytest.mark.parametrize("name,args,kw", CASES, ids=[c[0] for c in CASES])
def test_tpu_backend_routes_to_compiled_pallas(name, args, kw):
    assert registry.resolve_mode(
        name, *args, backend="tpu", **kw
    ) == registry.MODE_PALLAS


INELIGIBLE = [
    # no blocked structure -> the kernel's grid cannot be built
    ("bitset_spmm", _graph_args()[:5] + (None,), {}),
    # fused wave without a blocked structure -> scan-based oracle
    ("bitset_wave", _wave_args()[:6] + (None,), {}),
    # NT % tile_n != 0
    ("segment_agg", _seg_args(nt=6), {}),
    # S not divisible by the kv block
    ("flash_attention", _attn_args(s=300), {"causal": True, "window": None,
                                            "block_q": 128, "block_k": 128}),
    # d_qk != d_v (MLA regime) — kernel assumes same dims
    ("flash_attention",
     (_attn_args()[0], _attn_args()[1], _attn_args()[2][..., :64]),
     {"causal": True, "window": None, "block_q": 128, "block_k": 128}),
]


@pytest.mark.parametrize("name,args,kw", INELIGIBLE,
                         ids=["no-blocked", "wave-no-blocked",
                              "tile-misaligned", "seq-misaligned",
                              "dqk-ne-dv"])
def test_ineligible_shapes_route_to_ref_even_forced(name, args, kw):
    assert registry.resolve_mode(
        name, *args, force_pallas=True, backend="cpu", **kw
    ) == registry.MODE_REF
    assert registry.resolve_mode(
        name, *args, backend="tpu", **kw
    ) == registry.MODE_REF


# ---------------------------------------------------------------- parity
def test_bitset_wave_parity_through_wrapper():
    vals, src, dst, n, active, cand, bs = _wave_args()
    got = ops.bitset_wave(vals, src, dst, n, active, cand,
                          blocked=bs, force_pallas=True)
    want = ops.bitset_wave(vals, src, dst, n, active, cand, blocked=None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitset_wave_equals_hop_by_hop_spmm():
    # the fused L-hop wave must equal L single-hop bitset_spmm aggregations
    # with the per-hop candidacy mask applied in between
    vals, src, dst, n, active, cand, bs = _wave_args(hops=4)
    got = ops.bitset_wave(vals, src, dst, n, active, cand,
                          blocked=bs, force_pallas=True)
    step = vals
    for r in range(cand.shape[0]):
        agg = ops.bitset_or_aggregate(step, src, dst, n, active, blocked=None)
        step = agg & cand[r][:, None]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(step))


def test_bitset_spmm_parity_through_wrapper():
    vals, src, dst, n, active, bs = _graph_args()
    got = ops.bitset_or_aggregate(vals, src, dst, n, active,
                                  blocked=bs, force_pallas=True)
    want = ops.bitset_or_aggregate(vals, src, dst, n, active, blocked=None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_agg_parity_through_wrapper():
    feats, mask = _seg_args()
    deg = jnp.sum(mask, axis=1).astype(jnp.float32)
    got = ops.neighborhood_agg(feats, mask, deg, force_pallas=True)
    want = ops.neighborhood_agg(feats, mask, deg, force_pallas=False)
    for key in ("sum", "mean", "min", "max", "std"):
        np.testing.assert_allclose(np.asarray(got[key]), np.asarray(want[key]),
                                   rtol=2e-5, atol=2e-5)


def test_attention_parity_through_wrapper():
    q, k, v = _attn_args()
    got = ops.attention(q, k, v, causal=True, force_pallas=True)
    want = ops.attention(q, k, v, causal=True, force_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_embedding_bag_parity_through_wrapper():
    table, ids, weights = _bag_args()
    got = ops.embedding_bag(table, ids, weights, mode="mean", force_pallas=True)
    want = ops.embedding_bag(table, ids, weights, mode="mean")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------- no hidden fallback
@pytest.mark.parametrize("backend,force", [("tpu", False), ("cpu", True)],
                         ids=["compiled", "interpret"])
def test_failing_kernel_raises_instead_of_running_the_oracle(backend, force):
    calls = {"pallas": 0, "ref": 0}

    def broken_pallas(x, *, interpret):
        calls["pallas"] += 1
        raise NotImplementedError("simulated lowering failure")

    def oracle(x):
        calls["ref"] += 1
        return x + 1

    registry.register("_test_broken", pallas=broken_pallas, ref=oracle)
    try:
        with registry.count_dispatches() as counts:
            with pytest.raises(NotImplementedError, match="simulated"):
                registry.dispatch("_test_broken", jnp.asarray(1),
                                  force_pallas=force, backend=backend)
        assert calls == {"pallas": 1, "ref": 0}
        mode = registry.MODE_PALLAS if backend == "tpu" else registry.MODE_INTERPRET
        assert counts == {("_test_broken", mode): 1}
    finally:
        registry._REGISTRY.pop("_test_broken", None)


def test_ineligible_shape_routes_to_ref_and_is_counted():
    vals, src, dst, n, active, bs = _graph_args()
    with registry.count_dispatches() as counts:
        got = ops.bitset_or_aggregate(vals, src, dst, n, active, blocked=None,
                                      force_pallas=True)
        ops.bitset_or_aggregate(vals, src, dst, n, active, blocked=bs,
                                force_pallas=True)
    assert registry.dispatch_report(counts) == {
        "bitset_spmm:interpret": 1, "bitset_spmm:ref": 1}
    want = ref.bitset_spmm_ref(vals, src, dst, n, active)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_unknown_kernel_name_is_a_clear_error():
    with pytest.raises(KeyError, match="no kernel"):
        registry.dispatch("nope", 1)


# ---------------------------------------------------------------- compat
def test_make_mesh_accepts_axis_type_names():
    n = len(jax.devices())
    mesh = compat.make_mesh((n,), ("data",), axis_types=("auto",))
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == n


def test_shard_map_resolves_on_this_jax():
    mesh = compat.make_mesh((1,), ("x",))
    from jax.sharding import PartitionSpec as P

    f = compat.shard_map(lambda a: a * 2, mesh=mesh,
                         in_specs=(P(),), out_specs=P(), check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(f(jnp.arange(4))), np.arange(4) * 2)


def test_tpu_compiler_params_resolves_dimension_semantics():
    params = compat.tpu_compiler_params(dimension_semantics=("arbitrary",),
                                        vmem_limit_bytes=2**20)
    assert tuple(params.dimension_semantics) == ("arbitrary",)
    assert params.vmem_limit_bytes == 2**20


# ----------------------------------------- packed NLCC frontier integration
def test_packed_walk_constraint_matches_boolean_plane():
    from repro.core import Template, init_state
    from repro.core.nlcc import (
        check_walk_constraint, check_walk_constraint_packed,
    )
    from repro.core.state import PruneState

    g = gen.erdos_renyi_graph(120, 5.0, seed=9, n_labels=3)
    dg = DeviceGraph.from_host(g)
    tmpl = Template([0, 1, 2], [(0, 1), (1, 2), (2, 0)])
    st = init_state(dg, tmpl)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst),
                                 g.n, bn=64)
    walk = (0, 1, 2, 0)
    cand = jnp.stack([st.omega[:, q] for q in walk], axis=0)
    sources = np.flatnonzero(np.asarray(st.omega[:, 0]))[:32]
    ids = np.full(32, -1, np.int64)
    ids[: sources.size] = sources
    ids = jnp.asarray(ids, jnp.int32)

    want, _ = check_walk_constraint(dg, st, cand, True, ids)
    got = check_walk_constraint_packed(dg, st, cand, True, ids, bs)
    got_forced = check_walk_constraint_packed(
        dg, st, cand, True, ids, bs, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_forced), np.asarray(want))


def test_prune_with_blocked_structure_matches_default():
    from repro.core import Template, prune

    g = gen.erdos_renyi_graph(100, 5.0, seed=3, n_labels=3)
    tmpl = Template([0, 1, 2], [(0, 1), (1, 2), (2, 0)])
    dg = DeviceGraph.from_host(g)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst),
                                 g.n, bn=64)
    base = prune(g, tmpl)
    packed = prune(g, tmpl, blocked=bs)
    np.testing.assert_array_equal(base.omega, packed.omega)
    np.testing.assert_array_equal(base.vertex_mask, packed.vertex_mask)
    np.testing.assert_array_equal(base.edge_mask, packed.edge_mask)


# --------------------------------------------- fused NLCC wave engine
def _nlcc_setup(n=120, seed=9, bn=64):
    from repro.core import Template, init_state

    g = gen.erdos_renyi_graph(n, 5.0, seed=seed, n_labels=3)
    dg = DeviceGraph.from_host(g)
    tmpl = Template([0, 1, 2], [(0, 1), (1, 2), (2, 0)])
    st = init_state(dg, tmpl)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst),
                                 g.n, bn=bn)
    return g, dg, tmpl, st, bs


def _wave_ids(st, q0, wave, limit=None):
    sources = np.flatnonzero(np.asarray(st.omega[:, q0]))[: limit or wave]
    ids = np.full(wave, -1, np.int64)
    ids[: sources.size] = sources
    return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize("walk,is_cyclic", [
    ((0, 1, 2, 0), True),   # cyclic: token must return to its source
    ((0, 1, 2), False),     # path: the paper's ack at a different vertex
], ids=["cyclic", "path"])
@pytest.mark.parametrize("wave,limit", [
    (32, None),   # word-aligned, fully populated
    (64, 10),     # padded wave: sources < wave
], ids=["aligned", "padded"])
def test_fused_wave_matches_boolean_plane(walk, is_cyclic, wave, limit):
    from repro.core.nlcc import (
        check_walk_constraint, check_walk_constraint_fused,
    )

    g, dg, tmpl, st, bs = _nlcc_setup()
    cand = jnp.stack([st.omega[:, q] for q in walk], axis=0)
    ids = _wave_ids(st, walk[0], wave, limit)

    want, _ = check_walk_constraint(dg, st, cand, is_cyclic, ids)
    got = check_walk_constraint_fused(dg, st, cand, is_cyclic, ids, bs)
    got_forced = check_walk_constraint_fused(
        dg, st, cand, is_cyclic, ids, bs, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_forced), np.asarray(want))


def test_fused_wave_empty_frontier_and_all_pruned_sources():
    from repro.core.nlcc import check_walk_constraint_fused

    g, dg, tmpl, st, bs = _nlcc_setup()
    walk = (0, 1, 2, 0)
    cand = jnp.stack([st.omega[:, q] for q in walk], axis=0)
    # empty frontier: every wave slot is padding
    empty = jnp.full((32,), -1, jnp.int32)
    for force in (False, True):
        out = check_walk_constraint_fused(
            dg, st, cand, True, empty, bs, force_pallas=force)
        assert not np.asarray(out).any()
    # all-pruned sources: head candidacy fully eliminated kills every token
    ids = _wave_ids(st, walk[0], 32)
    dead = cand.at[0].set(jnp.zeros_like(cand[0]))
    for force in (False, True):
        out = check_walk_constraint_fused(
            dg, st, dead, True, ids, bs, force_pallas=force)
        assert not np.asarray(out).any()


def test_fused_route_gates_fall_back_to_unpacked():
    from repro.core.nlcc import nlcc_resolved_route, NLCC_ROUTE

    g, dg, tmpl, st, bs = _nlcc_setup()
    pol = registry.DispatchPolicy()
    pol.set_route(NLCC_ROUTE, "cpu", registry.BUCKET_ANY, registry.ROUTE_FUSED)
    registry.set_policy(pol)
    try:
        assert nlcc_resolved_route(st, 32, bs) == registry.ROUTE_FUSED
        # capability gates beat the tuned fused choice
        assert nlcc_resolved_route(st, 32, None) == registry.ROUTE_UNPACKED
        assert nlcc_resolved_route(st, 33, bs) == registry.ROUTE_UNPACKED
        assert nlcc_resolved_route(
            st, 32, bs, count_messages=True) == registry.ROUTE_UNPACKED
        # force_pallas still pins the per-hop packed parity path
        assert nlcc_resolved_route(
            st, 32, bs, force_pallas=True) == registry.ROUTE_PACKED
    finally:
        registry.set_policy(None)


def test_prune_fused_route_matches_default_and_reports_waves():
    from repro.core import Template, prune
    from repro.core.nlcc import NLCC_ROUTE

    g, dg, tmpl, st, bs = _nlcc_setup(seed=3, n=100)
    registry.set_policy(None)
    base = prune(g, tmpl, blocked=bs)
    pol = registry.DispatchPolicy()
    pol.set_route(NLCC_ROUTE, "cpu", registry.BUCKET_ANY, registry.ROUTE_FUSED)
    registry.set_policy(pol)
    try:
        fused = prune(g, tmpl, blocked=bs)
    finally:
        registry.set_policy(None)
    assert fused.stats["dispatch_routes"][NLCC_ROUTE] == registry.ROUTE_FUSED
    fused_waves = sum(p.extra.get("nlcc_fused_waves", 0) for p in fused.phases)
    other_waves = sum(p.extra.get("nlcc_packed_waves", 0)
                      + p.extra.get("nlcc_plane_waves", 0)
                      for p in fused.phases)
    assert fused_waves > 0 and other_waves == 0
    np.testing.assert_array_equal(base.omega, fused.omega)
    np.testing.assert_array_equal(base.edge_mask, fused.edge_mask)


def test_wave_executor_syncs_host_at_most_twice_per_constraint():
    """The acceptance contract: survivors accumulate on device — host syncs
    per CC/PC constraint stay bounded (head-candidacy read + optional message
    readback) no matter how many waves the constraint takes."""
    from repro.core import Template, prune

    g, dg, tmpl, st, bs = _nlcc_setup()
    # wave=32 forces many waves per constraint (~40 sources per label)
    res = prune(g, tmpl, wave=32, blocked=bs)
    stats_sum = {}
    for p in res.phases:
        for k, v in p.extra.items():
            stats_sum[k] = stats_sum.get(k, 0) + v
    n_constraints = stats_sum.get("nlcc_constraints", 0)
    n_waves = stats_sum.get("nlcc_waves", 0)
    assert n_constraints > 0 and n_waves > n_constraints
    assert stats_sum["nlcc_host_syncs"] <= 2 * n_constraints

    # the instrumented path may add exactly one message readback
    res2 = prune(g, tmpl, wave=32, collect_stats=True)
    stats_sum2 = {}
    for p in res2.phases:
        for k, v in p.extra.items():
            stats_sum2[k] = stats_sum2.get(k, 0) + v
    assert stats_sum2["nlcc_host_syncs"] <= 2 * stats_sum2["nlcc_constraints"]


def test_fused_route_packs_once_per_wave(monkeypatch):
    """Pack/unpack must happen once per wave on the fused route — not once
    per hop (the per-hop oracle round-trip the fused engine eliminates)."""
    from repro.core import state as state_mod
    from repro.core.nlcc import check_walk_constraint_fused

    g, dg, tmpl, st, bs = _nlcc_setup()
    walk = (0, 1, 2, 0)  # 3 hops
    cand = jnp.stack([st.omega[:, q] for q in walk], axis=0)
    ids = _wave_ids(st, 0, 32)

    calls = {"pack": 0, "unpack": 0}
    real_pack, real_unpack = state_mod.pack_bits, state_mod.unpack_bits

    def counting_pack(x):
        calls["pack"] += 1
        return real_pack(x)

    def counting_unpack(x, n0):
        calls["unpack"] += 1
        return real_unpack(x, n0)

    monkeypatch.setattr(state_mod, "pack_bits", counting_pack)
    monkeypatch.setattr(state_mod, "unpack_bits", counting_unpack)
    for force in (False, True):  # scan-based oracle AND interpret-mode kernel
        calls["pack"] = calls["unpack"] = 0
        check_walk_constraint_fused(
            dg, st, cand, True, ids, bs, force_pallas=force)
        assert calls == {"pack": 1, "unpack": 1}
