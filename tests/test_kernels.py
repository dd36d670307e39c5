"""Per-kernel correctness: interpret-mode Pallas vs pure-jnp oracle,
swept over shapes and dtypes (deliverable (c))."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.graph.blocked import build_blocked_structure, masks_from_active, pad_values
from repro.graph.structs import Graph, DeviceGraph
from repro.graph import generators as gen
from repro.kernels import ref
from repro.kernels import ops
from repro.kernels.bitset_spmm import bitset_spmm
from repro.kernels.segment_agg import segment_agg
from repro.kernels.flash_attention import flash_attention
from repro.kernels.embedding_bag import embedding_bag


# ------------------------------------------------------------- bitset_spmm
@pytest.mark.parametrize("scale,w,bn", [(6, 1, 64), (7, 2, 128), (8, 4, 64), (6, 8, 32)])
def test_bitset_spmm_matches_ref(scale, w, bn):
    g = gen.rmat_graph(scale, edge_factor=4, seed=scale + w)
    dg = DeviceGraph.from_host(g)
    rng = np.random.default_rng(scale * 10 + w)
    vals = jnp.asarray(rng.integers(0, 2**32, size=(g.n, w), dtype=np.uint32))
    active = jnp.asarray(rng.random(dg.m) < 0.7)

    want = ref.bitset_spmm_ref(vals, dg.src, dg.dst, g.n, active)

    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=bn)
    got = ops.bitset_or_aggregate(
        vals, dg.src, dg.dst, g.n, active, blocked=bs, force_pallas=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("chunk,p_active", [(1, 0.7), (3, 0.7), (7, 0.05)])
def test_bitset_spmm_chunked_sweep_matches_ref(chunk, p_active):
    # chunks cut dst rows anywhere (a row's blocks then span two kernel
    # calls) and dead blocks are skipped; neither may change a word
    g = gen.rmat_graph(7, edge_factor=4, seed=chunk)
    dg = DeviceGraph.from_host(g)
    rng = np.random.default_rng(chunk)
    vals = jnp.asarray(rng.integers(0, 2**32, size=(g.n, 2), dtype=np.uint32))
    active = jnp.asarray(rng.random(dg.m) < p_active)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=32)
    got = bitset_spmm(
        jnp.asarray(bs.pairs), masks_from_active(bs, active), pad_values(vals, bs),
        bn=bs.bn, n_pad=bs.n_pad, interpret=True, chunk=chunk)[:g.n]
    want = ref.bitset_spmm_ref(vals, dg.src, dg.dst, g.n, active)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("w", [1, 3])
def test_bitset_spmm_ref_matches_numpy_or_scatter(w):
    g = gen.erdos_renyi_graph(150, 2.0, seed=w)  # some vertices have no in-arcs
    dg = DeviceGraph.from_host(g)
    rng = np.random.default_rng(w)
    vals = rng.integers(0, 2**32, size=(g.n, w), dtype=np.uint32)
    active = rng.random(dg.m) < 0.6
    src, dst = np.asarray(dg.src), np.asarray(dg.dst)
    want = np.zeros_like(vals)
    np.bitwise_or.at(want, dst[active], vals[src[active]])
    got = ref.bitset_spmm_ref(jnp.asarray(vals), dg.src, dg.dst, g.n,
                              jnp.asarray(active))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("hub_deg", [1, 37, 1000])
def test_segment_or_matches_numpy_on_long_segments(hub_deg):
    # the doubling scan must carry a segment across many shifts (a hub
    # with hub_deg in-arcs) and leave vertices with no in-arcs at zero
    from repro.graph import segment_ops

    rng = np.random.default_rng(hub_deg)
    dst = np.sort(np.concatenate([np.full(hub_deg, 5), rng.integers(0, 40, 301)]))
    vals = rng.integers(0, 2**32, size=(dst.size, 2), dtype=np.uint32)
    want = np.zeros((41, 2), np.uint32)
    np.bitwise_or.at(want, dst, vals)
    meta = segment_ops.build_segment_meta(dst, 41)
    got = segment_ops.segment_or(jnp.asarray(vals), meta, 41)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_blocked_device_arrays_first_used_inside_a_trace():
    g = gen.erdos_renyi_graph(200, 4.0, seed=2)
    dg = DeviceGraph.from_host(g)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=64)
    active = jnp.asarray(np.random.default_rng(2).random(dg.m) < 0.5)
    inside = jax.jit(lambda a: masks_from_active(bs, a))(active)
    outside = masks_from_active(bs, active)  # reuses the cached arrays
    np.testing.assert_array_equal(np.asarray(inside), np.asarray(outside))


def test_unique_pairs_matches_rowwise_unique():
    from repro.graph.structs import unique_pairs

    pairs = np.random.default_rng(0).integers(0, 50, size=(2000, 2))
    a, b = unique_pairs(pairs[:, 0], pairs[:, 1])
    np.testing.assert_array_equal(np.stack([a, b], axis=1),
                                  np.unique(pairs, axis=0))


def test_blocked_structure_rejects_non_power_of_two_block():
    with pytest.raises(ValueError, match="power of two"):
        build_blocked_structure(np.zeros(1, np.int32), np.zeros(1, np.int32), 4, bn=96)


def test_bitset_spmm_all_edges_inactive():
    g = gen.erdos_renyi_graph(100, 4.0, seed=0)
    dg = DeviceGraph.from_host(g)
    vals = jnp.ones((g.n, 1), jnp.uint32)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=32)
    got = ops.bitset_or_aggregate(
        vals, dg.src, dg.dst, g.n, jnp.zeros(dg.m, bool), blocked=bs, force_pallas=True
    )
    assert int(np.asarray(got).sum()) == 0


# ------------------------------------------------------------- bitset_wave
@pytest.mark.parametrize("scale,w,bn,hops", [
    (6, 1, 64, 1),    # single hop degenerates to bitset_spmm + mask
    (7, 2, 128, 3),
    (8, 4, 64, 5),
    (6, 8, 32, 2),
])
def test_bitset_wave_matches_ref(scale, w, bn, hops):
    g = gen.rmat_graph(scale, edge_factor=4, seed=scale + w)
    dg = DeviceGraph.from_host(g)
    rng = np.random.default_rng(scale * 10 + w + hops)
    vals = jnp.asarray(rng.integers(0, 2**32, size=(g.n, w), dtype=np.uint32))
    active = jnp.asarray(rng.random(dg.m) < 0.7)
    cand = jnp.asarray(np.where(
        rng.random((hops, g.n)) < 0.8, np.uint32(0xFFFFFFFF), np.uint32(0)))

    want = ref.bitset_wave_ref(vals, dg.src, dg.dst, g.n, active, cand)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=bn)
    got = ops.bitset_wave(
        vals, dg.src, dg.dst, g.n, active, cand, blocked=bs, force_pallas=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitset_wave_ref_equals_iterated_spmm_ref():
    # the scan-based packed-word oracle against L iterations of the
    # single-hop oracle with the candidacy mask applied between hops
    g = gen.erdos_renyi_graph(200, 5.0, seed=11)
    dg = DeviceGraph.from_host(g)
    rng = np.random.default_rng(11)
    vals = jnp.asarray(rng.integers(0, 2**32, size=(g.n, 2), dtype=np.uint32))
    active = jnp.asarray(rng.random(dg.m) < 0.6)
    cand = jnp.asarray(np.where(
        rng.random((4, g.n)) < 0.75, np.uint32(0xFFFFFFFF), np.uint32(0)))
    got = ref.bitset_wave_ref(vals, dg.src, dg.dst, g.n, active, cand)
    step = vals
    for r in range(cand.shape[0]):
        step = ref.bitset_spmm_ref(step, dg.src, dg.dst, g.n, active) & cand[r][:, None]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(step))


def test_bitset_wave_all_edges_inactive():
    g = gen.erdos_renyi_graph(100, 4.0, seed=0)
    dg = DeviceGraph.from_host(g)
    vals = jnp.ones((g.n, 1), jnp.uint32)
    cand = jnp.full((2, g.n), 0xFFFFFFFF, jnp.uint32)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=32)
    for force in (False, True):
        got = ops.bitset_wave(
            vals, dg.src, dg.dst, g.n, jnp.zeros(dg.m, bool), cand,
            blocked=bs, force_pallas=force)
        assert int(np.asarray(got).sum()) == 0


def test_bitset_wave_zero_hops_is_identity():
    vals = jnp.asarray(
        np.random.default_rng(0).integers(0, 2**32, size=(16, 2), dtype=np.uint32))
    src = jnp.zeros((0,), jnp.int32)
    dst = jnp.zeros((0,), jnp.int32)
    cand = jnp.zeros((0, 16), jnp.uint32)
    out = ops.bitset_wave(vals, src, dst, 16, jnp.zeros((0,), bool), cand)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(vals))


def test_bitset_wave_vmem_budget_gates_eligibility():
    from repro.kernels.ops import (
        _wave_eligible, bitset_wave_vmem_bytes, BITSET_WAVE_VMEM_BUDGET)

    g = gen.erdos_renyi_graph(256, 3.0, seed=1)
    dg = DeviceGraph.from_host(g)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=64)
    small = jnp.ones((g.n, 2), jnp.uint32)
    cand = jnp.ones((2, g.n), jnp.uint32)
    assert _wave_eligible(small, dg.src, dg.dst, g.n, None, cand, bs)
    # a frontier too wide to keep resident in VMEM must route to the oracle
    huge_w = 128
    while bitset_wave_vmem_bytes(bs.n_pad, huge_w, bs.bn) <= BITSET_WAVE_VMEM_BUDGET:
        huge_w += 128
    huge = jnp.ones((g.n, huge_w), jnp.uint32)
    assert not _wave_eligible(huge, dg.src, dg.dst, g.n, None, cand, bs)
    assert not _wave_eligible(small, dg.src, dg.dst, g.n, None, cand, None)
    # lane padding: every packed width up to 128 words holds the same planes
    assert (bitset_wave_vmem_bytes(8192, 1, 64) - 6 * 64 * 128 * 4
            == bitset_wave_vmem_bytes(8192, 32, 64) - 6 * 64 * 1024 * 4)


def test_blocked_masks_roundtrip():
    """Every (src,dst) arc must land on exactly its bit."""
    g = gen.erdos_renyi_graph(300, 5.0, seed=3)
    dg = DeviceGraph.from_host(g)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=64)
    masks = np.asarray(masks_from_active(bs, jnp.ones(dg.m, bool))).view(np.uint32)
    src, dst = np.asarray(dg.src), np.asarray(dg.dst)
    total_bits = sum(bin(int(x)).count("1") for x in masks.reshape(-1))
    assert total_bits == dg.m
    for e in np.random.default_rng(0).integers(0, dg.m, 20):
        b = bs.edge_block[e]
        r, c = dst[e] % bs.bn, src[e] % bs.bn
        assert (masks[b, 0, r * bs.bnw + c // 32] >> (c % 32)) & 1 == 1


# ------------------------------------------------------------- segment_agg
@pytest.mark.parametrize("nt,d,f,dtype", [
    (16, 10, 128, jnp.float32),
    (8, 25, 256, jnp.float32),
    (32, 4, 128, jnp.bfloat16),
])
def test_segment_agg_matches_ref(nt, d, f, dtype):
    rng = np.random.default_rng(nt + d)
    feats = jnp.asarray(rng.standard_normal((nt, d, f)), dtype)
    mask = jnp.asarray(rng.random((nt, d)) < 0.8)
    got = segment_agg(feats, mask, interpret=True)
    want = ref.segment_agg_ref(feats, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_neighborhood_agg_stats():
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.standard_normal((8, 6, 128)), jnp.float32)
    mask = jnp.ones((8, 6), bool).at[0, 3:].set(False).at[1].set(False)
    deg = jnp.sum(mask, axis=1).astype(jnp.float32)
    out = ops.neighborhood_agg(feats, mask, deg, force_pallas=True)
    x0 = np.asarray(feats)[0, :3]
    np.testing.assert_allclose(np.asarray(out["mean"][0]), x0.mean(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["std"][0]), x0.std(0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out["max"][1]), 0.0)  # empty segment


# --------------------------------------------------------- flash_attention
@pytest.mark.parametrize("b,hq,hkv,s,d,causal,window", [
    (1, 4, 4, 256, 128, True, None),    # MHA causal
    (2, 8, 2, 256, 128, True, None),    # GQA
    (1, 4, 1, 384, 128, False, None),   # MQA bidirectional
    (1, 2, 2, 512, 128, True, 128),     # sliding window (StarCoder2 regime)
    (1, 2, 2, 256, 256, True, None),    # wide head dim
    (3, 6, 3, 128, 128, True, 64),      # GQA + window, odd batch
])
def test_flash_attention_matches_ref(b, hq, hkv, s, d, causal, window):
    rng = np.random.default_rng(hq * s)
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)) * 0.3, jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 128)) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 128)) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 128)) * 0.3, jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
    )


# ----------------------------------------------------------- embedding_bag
@pytest.mark.parametrize("v,d,b,l,mode", [
    (1000, 128, 8, 4, "sum"),
    (5000, 256, 16, 10, "mean"),
    (128, 128, 4, 1, "sum"),
    (2048, 512, 2, 32, "mean"),   # long bags, wide rows
])
def test_embedding_bag_matches_ref(v, d, b, l, mode):
    rng = np.random.default_rng(v + b)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, size=(b, l)), jnp.int32)
    weights = jnp.asarray((rng.random((b, l)) < 0.9), jnp.float32)  # some padding
    got = embedding_bag(table, ids, weights, mode=mode, interpret=True)
    want = ref.embedding_bag_ref(table, ids, weights, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_lcc_fixpoint_packed_engine_parity():
    """The engine's packed-word LCC (bitset_spmm kernel path) must reach the
    same fixpoint as the boolean-plane reference iteration."""
    from repro.core.state import init_state
    from repro.core.template import Template
    from repro.core.lcc import TemplateDev, lcc_iteration, lcc_iteration_packed

    g = gen.rmat_graph(8, edge_factor=6, seed=4, labeler="random", n_labels=4)
    dg = DeviceGraph.from_host(g)
    tmpl = Template([0, 1, 2, 3], [(0, 1), (1, 2), (2, 3), (3, 0)])
    tdev = TemplateDev(tmpl)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=64)

    st_ref = st_pk = init_state(dg, tmpl)
    for _ in range(20):
        st_ref, ch_ref = lcc_iteration(dg, tdev, st_ref)
        st_pk, ch_pk = lcc_iteration_packed(dg, tdev, st_pk, bs, force_pallas=True)
        np.testing.assert_array_equal(np.asarray(st_ref.omega), np.asarray(st_pk.omega))
        np.testing.assert_array_equal(
            np.asarray(st_ref.edge_active), np.asarray(st_pk.edge_active))
        if not bool(ch_ref):
            break
    assert not bool(ch_ref) and not bool(ch_pk)


def test_lcc_sweep_via_bitset_kernel_equals_segment_path():
    """The engine's LCC OR-aggregation through the kernel path must equal the
    boolean-plane segment path used by lcc.py."""
    from repro.core.state import pack_bits, unpack_bits, init_state
    from repro.core.template import Template
    from repro.graph import segment_ops

    g = gen.erdos_renyi_graph(200, 6.0, seed=5, n_labels=3)
    dg = DeviceGraph.from_host(g)
    tmpl = Template([0, 1, 2], [(0, 1), (1, 2), (2, 0)])
    st = init_state(dg, tmpl)
    packed = pack_bits(st.omega)
    bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), g.n, bn=64)
    got = ops.bitset_or_aggregate(
        packed, dg.src, dg.dst, g.n, st.edge_active, blocked=bs, force_pallas=True
    )
    msgs = jnp.take(st.omega, dg.src, axis=0) & st.edge_active[:, None]
    want = segment_ops.segment_or_bool(msgs, dg.dst, g.n)
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(got, tmpl.n0)), np.asarray(want)
    )
