"""Jit'd public wrappers for the Pallas kernels, routed through the registry.

Dispatch contract (single choke point — `repro.kernels.registry.dispatch`):
every wrapper below registers its Pallas entrypoint, its pure-jnp oracle from
ref.py, and a shape-eligibility predicate; per call the registry picks exactly
one of pallas-compiled (eligible + TPU backend), pallas-interpret (eligible +
force_pallas off-TPU — the kernel-parity test path), or the reference oracle
(ineligible shapes, or off-TPU without force_pallas). A Pallas call that
fails raises; `registry.count_dispatches()` shows which mode each call took.

The wrappers own only pre/post-processing that is mode-independent (blocked
mask construction, PNA mean/std derivation, long-sequence blockwise choice).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.graph.blocked import BlockedStructure, masks_from_active, pad_values
from repro.kernels import ref as _ref
from repro.kernels import registry
from repro.kernels.bitset_spmm import bitset_spmm as _bitset_spmm_pallas
from repro.kernels.bitset_wave import (
    BITSET_WAVE_MAX_BLOCKS,
    BITSET_WAVE_VMEM_BUDGET,
    bitset_wave as _bitset_wave_pallas,
)
from repro.kernels.segment_agg import (
    TILE_F as SEGMENT_AGG_TILE_F,
    TILE_N as SEGMENT_AGG_TILE_N,
    segment_agg as _segment_agg_pallas,
)
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.embedding_bag import embedding_bag as _embedding_bag_pallas

# Sequences longer than this lower the flash-semantics XLA path on the ref
# side (O(S * block) live memory) instead of the materialized S x S oracle.
ATTENTION_BLOCKWISE_CUTOFF = 2048


# ------------------------------------------------------------- bitset_spmm
def _bitset_pallas(vals, dg_src, dg_dst, n, edge_active, blocked, *, interpret):
    masks = masks_from_active(blocked, edge_active)
    out = _bitset_spmm_pallas(
        blocked.device_arrays[0], masks, pad_values(vals, blocked),
        bn=blocked.bn, n_pad=blocked.n_pad, interpret=interpret,
    )
    return out[:n]


def _bitset_ref(vals, dg_src, dg_dst, n, edge_active, blocked):
    return _ref.bitset_spmm_ref(vals, dg_src, dg_dst, n, edge_active)


registry.register(
    "bitset_spmm",
    pallas=_bitset_pallas,
    ref=_bitset_ref,
    eligible=lambda vals, dg_src, dg_dst, n, edge_active, blocked: (
        blocked is not None
    ),
    # tuned decisions are shared per (vertex-count, packed-width) bucket: the
    # LCC sweep (W = ceil(n0/32)) and the NLCC wave hop (W = wave/32) land in
    # different buckets and may legitimately pick different modes
    bucket=lambda vals, dg_src, dg_dst, n, edge_active, blocked: (
        registry.shape_bucket(n) + (int(vals.shape[-1]),)
    ),
    doc="blocked bit-packed OR-SpMM (LCC/NLCC edge sweep)",
)


def bitset_or_aggregate(
    vals: jnp.ndarray,          # uint32[n, W] packed per-vertex words
    dg_src: jnp.ndarray,        # int32[m] dst-sorted
    dg_dst: jnp.ndarray,
    n: int,
    edge_active: jnp.ndarray,   # bool[m]
    blocked: Optional[BlockedStructure] = None,
    force_pallas: bool = False,
) -> jnp.ndarray:
    """OR-aggregate packed words along active arcs -> uint32[n, W]."""
    return registry.dispatch(
        "bitset_spmm", vals, dg_src, dg_dst, n, edge_active, blocked,
        force_pallas=force_pallas,
    )


# ------------------------------------------------------------- bitset_wave
def _lanes(x: int) -> int:
    return -(-x // 128) * 128


def bitset_wave_vmem_bytes(n_pad: int, w: int, bn: int) -> int:
    """VMEM the fused wave kernel needs for one shape, counted the way Mosaic
    lays it out (lane dims padded to 128, pipelined blocks double-buffered):
    the two resident frontier planes, the f32 accumulator plus the unpacked
    bf16 planes and pack/unpack temporaries (six [bn, 32W] f32-sized slabs),
    the mask and candidacy blocks, and the unpacked [bn, bn] mask. It bounds
    the smallest `vmem_limit_bytes` the v5e compiler accepts for the shape
    (tests/test_tpu_compile.py compiles the largest admitted shape)."""
    frontier = 2 * n_pad * _lanes(w) * 4
    slabs = 6 * bn * _lanes(32 * w) * 4
    blocks = 2 * bn * _lanes(bn // 32) * 4 + 2 * bn * _lanes(1) * 4
    return frontier + slabs + blocks + bn * _lanes(bn) * 4


def _wave_pallas(vals, dg_src, dg_dst, n, edge_active, cand, blocked,
                 *, interpret):
    # masks are built ONCE per wave — edge_active is constant across hops —
    # where the per-hop route rebuilds them around every bitset_spmm launch
    if blocked.nnzb == 0 or cand.shape[0] == 0:
        return jnp.zeros_like(vals) if cand.shape[0] else vals
    masks = masks_from_active(blocked, edge_active)
    cand_pad = jnp.zeros((cand.shape[0], blocked.n_pad), jnp.uint32)
    cand_pad = cand_pad.at[:, :n].set(cand)
    out = _bitset_wave_pallas(
        blocked.device_arrays[0], masks, pad_values(vals, blocked), cand_pad,
        bn=blocked.bn, n_pad=blocked.n_pad, interpret=interpret,
    )
    return out[:n]


def _wave_eligible(vals, dg_src, dg_dst, n, edge_active, cand, blocked):
    if blocked is None or blocked.nnzb > BITSET_WAVE_MAX_BLOCKS:
        return False
    need = bitset_wave_vmem_bytes(blocked.n_pad, int(vals.shape[-1]), blocked.bn)
    return need <= BITSET_WAVE_VMEM_BUDGET


registry.register(
    "bitset_wave",
    pallas=_wave_pallas,
    ref=lambda vals, dg_src, dg_dst, n, edge_active, cand, blocked: (
        _ref.bitset_wave_ref(vals, dg_src, dg_dst, n, edge_active, cand)
    ),
    eligible=_wave_eligible,
    # one decision per (vertex-count, packed-width, hop-count) bucket — the
    # NLCC wave width (W = wave/32) and walk length both shape the cost
    bucket=lambda vals, dg_src, dg_dst, n, edge_active, cand, blocked: (
        registry.shape_bucket(n) + (int(vals.shape[-1]), int(cand.shape[0]))
    ),
    doc="fused multi-hop bit-packed OR-SpMM (NLCC wave engine)",
)


def bitset_wave(
    vals: jnp.ndarray,          # uint32[n, W] packed initial frontier
    dg_src: jnp.ndarray,        # int32[m] dst-sorted
    dg_dst: jnp.ndarray,
    n: int,
    edge_active: jnp.ndarray,   # bool[m]
    cand: jnp.ndarray,          # uint32[L, n] per-hop candidacy, 0 / 0xFFFFFFFF
    blocked: Optional[BlockedStructure] = None,
    force_pallas: bool = False,
) -> jnp.ndarray:
    """Run the full L-hop NLCC wave in one kernel call -> uint32[n, W]."""
    if cand.shape[0] == 0:
        return vals
    return registry.dispatch(
        "bitset_wave", vals, dg_src, dg_dst, n, edge_active, cand, blocked,
        force_pallas=force_pallas,
    )


# ------------------------------------------------------------- segment_agg
def _segment_agg_eligible(feats, mask):
    nt, _, f = feats.shape
    return nt % SEGMENT_AGG_TILE_N == 0 and f % SEGMENT_AGG_TILE_F == 0


registry.register(
    "segment_agg",
    pallas=lambda feats, mask, *, interpret: _segment_agg_pallas(
        feats, mask, interpret=interpret
    ),
    ref=_ref.segment_agg_ref,
    eligible=_segment_agg_eligible,
    doc="fused sum/min/max/sumsq neighborhood aggregation (PNA bank)",
)


def neighborhood_agg(
    feats: jnp.ndarray,   # [NT, D, F] gathered neighbor features
    mask: jnp.ndarray,    # bool[NT, D]
    degrees: jnp.ndarray,  # f32[NT] true degrees (for mean/std)
    force_pallas: bool = False,
) -> dict:
    """Fused sum/mean/min/max/std neighborhood aggregation (PNA's bank)."""
    raw = registry.dispatch("segment_agg", feats, mask, force_pallas=force_pallas)
    s, mn, mx, sq = raw[:, 0], raw[:, 1], raw[:, 2], raw[:, 3]
    deg = jnp.maximum(degrees, 1.0)[:, None]
    empty = (degrees <= 0)[:, None]
    mean = s / deg
    var = jnp.maximum(sq / deg - mean * mean, 0.0)
    zero = jnp.zeros_like(s)
    return {
        "sum": s,
        "mean": mean,
        "min": jnp.where(empty, zero, mn),
        "max": jnp.where(empty, zero, mx),
        # +eps: sqrt has an infinite derivative at 0 (NaN in backward)
        "std": jnp.sqrt(var + 1e-12),
    }


# --------------------------------------------------------- flash_attention
def _attention_eligible(q, k, v, *, causal=True, window=None,
                        block_q=128, block_k=128):
    s = q.shape[2]
    return (
        s % block_q == 0 and s % block_k == 0
        and q.shape[3] >= 128 and q.shape[3] == v.shape[3]
    )


def _attention_ref(q, k, v, *, causal=True, window=None,
                   block_q=128, block_k=128):
    if q.shape[2] > ATTENTION_BLOCKWISE_CUTOFF:
        # flash-semantics XLA path: O(S * block) live memory; this is what the
        # dry-run lowers for long sequences on non-TPU backends (and the MLA
        # d_qk != d_v case everywhere).
        return _ref.attention_blockwise(q, k, v, causal=causal, window=window)
    return _ref.attention_ref(q, k, v, causal=causal, window=window)


registry.register(
    "flash_attention",
    pallas=lambda q, k, v, *, interpret, causal=True, window=None,
    block_q=128, block_k=128: _flash_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    ),
    ref=_attention_ref,
    eligible=_attention_eligible,
    doc="causal/GQA/sliding-window flash attention (LM hot loop)",
)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    force_pallas: bool = False,
) -> jnp.ndarray:
    return registry.dispatch(
        "flash_attention", q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, force_pallas=force_pallas,
    )


# ----------------------------------------------------------- embedding_bag
registry.register(
    "embedding_bag",
    pallas=lambda table, ids, weights, *, interpret, mode="sum": (
        _embedding_bag_pallas(table, ids, weights, mode=mode, interpret=interpret)
    ),
    ref=lambda table, ids, weights, *, mode="sum": (
        _ref.embedding_bag_ref(table, ids, weights, mode=mode)
    ),
    doc="scalar-prefetch gather + VMEM bag reduce (recsys hot loop)",
)


def embedding_bag(
    table: jnp.ndarray,
    ids: jnp.ndarray,
    weights: Optional[jnp.ndarray] = None,
    *,
    mode: str = "sum",
    force_pallas: bool = False,
) -> jnp.ndarray:
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    return registry.dispatch(
        "embedding_bag", table, ids, weights, mode=mode,
        force_pallas=force_pallas,
    )
