"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references (kernel tests sweep shapes/dtypes and
assert_allclose against them) AND the CPU/GPU fallback paths dispatched by
ops.py — the dry-run lowers these on non-TPU backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# ------------------------------------------------------------- bitset_spmm
def _packed_or_hop(src, dst, n, edge_active):
    """The OR-aggregate of packed words along the active dst-sorted arcs, as
    a function words[n, W] -> words[n, W]: a segmented associative OR-scan
    over the arcs, which stays in uint32 words (no bit planes)."""
    from repro.graph import segment_ops

    m = src.shape[0]
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), dst[1:] != dst[:-1]])
    last_edge = jnp.full((n,), -1, jnp.int32).at[dst].max(
        jnp.arange(m, dtype=jnp.int32))
    meta = segment_ops.SegmentMeta(
        is_start=is_start, last_edge_of_vertex=last_edge)
    ea_word = jnp.where(edge_active, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))

    def hop(packed):
        msgs = jnp.take(packed, src, axis=0) & ea_word[:, None]
        return segment_ops.segment_or(msgs, meta, n)

    return hop


@functools.partial(jax.jit, static_argnames=("n",))
def bitset_spmm_ref(
    vals: jnp.ndarray,         # uint32[n, W] packed
    src: jnp.ndarray,          # int32[m] dst-sorted
    dst: jnp.ndarray,          # int32[m]
    n: int,
    edge_active: jnp.ndarray,  # bool[m]
) -> jnp.ndarray:
    """out[v] = OR over active arcs (u -> v) of vals[u]."""
    if src.shape[0] == 0:
        return jnp.zeros((n, vals.shape[1]), jnp.uint32)
    return _packed_or_hop(src, dst, n, edge_active)(vals)


# ------------------------------------------------------------- bitset_wave
@functools.partial(jax.jit, static_argnames=("n",))
def bitset_wave_ref(
    vals: jnp.ndarray,         # uint32[n, W] packed initial frontier (hop 0)
    src: jnp.ndarray,          # int32[m] dst-sorted
    dst: jnp.ndarray,          # int32[m]
    n: int,
    edge_active: jnp.ndarray,  # bool[m]
    cand: jnp.ndarray,         # uint32[L, n] per-hop candidacy, 0 / 0xFFFFFFFF
) -> jnp.ndarray:
    """Fused L-hop wave: F_r = OR-aggregate(F_{r-1}) & cand[r], r = 1..L.

    Hops are a `lax.scan` over the hop-indexed candidacy stack of the packed
    hop of `bitset_spmm_ref`; the whole wave is one jitted XLA computation.
    """
    if cand.shape[0] == 0:
        return vals
    if src.shape[0] == 0:
        return jnp.zeros_like(vals)
    or_hop = _packed_or_hop(src, dst, n, edge_active)

    def hop(packed, cw):
        return or_hop(packed) & cw[:, None], None

    out, _ = jax.lax.scan(hop, vals, cand)
    return out


# ------------------------------------------------------------- segment_agg
def segment_agg_ref(feats: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """feats [NT, D, F], mask [NT, D] -> [NT, 4, F] sum/min/max/sumsq."""
    big = jnp.float32(3.0e38)
    x = feats.astype(jnp.float32)
    valid = mask[:, :, None]
    s = jnp.sum(jnp.where(valid, x, 0.0), axis=1)
    mn = jnp.min(jnp.where(valid, x, big), axis=1)
    mx = jnp.max(jnp.where(valid, x, -big), axis=1)
    sq = jnp.sum(jnp.where(valid, x * x, 0.0), axis=1)
    return jnp.stack([s, mn, mx, sq], axis=1)


# --------------------------------------------------------- flash_attention
def attention_ref(
    q: jnp.ndarray,  # [B, Hq, S, D]
    k: jnp.ndarray,  # [B, Hkv, S, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
) -> jnp.ndarray:
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    scale = 1.0 / (d ** 0.5)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(s)[None, :]
    live = jnp.ones((s, s), dtype=bool)
    if causal:
        live &= q_pos >= k_pos
    if window is not None:
        live &= k_pos > q_pos - window
    logits = jnp.where(live[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)).astype(q.dtype)


def attention_blockwise(
    q: jnp.ndarray,  # [B, Hq, S, D]
    k: jnp.ndarray,  # [B, Hkv, S, Dk]
    v: jnp.ndarray,  # [B, Hkv, S, Dv]
    *,
    causal: bool = True,
    window: int | None = None,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Flash-semantics attention in pure XLA: lax.scan over KV blocks with an
    online-softmax carry — O(S * block_k) live memory instead of O(S^2).

    This is what the dry-run lowers on non-TPU backends for long sequences, so
    the reported memory/roofline profile matches the Pallas kernel's algorithm
    (same FLOPs, same O(S) working set), not a materialized S x S matrix.
    Also handles d_qk != d_v (MLA)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    dv = v.shape[-1]
    scale = 1.0 / (d ** 0.5)
    nk = -(-s // block_k)
    pad = nk * block_k - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, hq, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hq, nk, block_k, dv).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(s)

    def body(carry, xs):
        m, l, acc = carry
        ki, kblk, vblk = xs
        k_pos = ki * block_k + jnp.arange(block_k)
        # dots in the input dtype (bf16 on the MXU) with fp32 accumulation —
        # matches the Pallas kernel's numerics and byte traffic
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, kblk,
                            preferred_element_type=jnp.float32) * scale
        live = (k_pos[None, :] < s)
        if causal:
            live = live & (q_pos[:, None] >= k_pos[None, :])
        if window is not None:
            live = live & (k_pos[None, :] > q_pos[:, None] - window)
        logits = jnp.where(live[None, None], logits, -1e30)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, hq, s), -1e30, jnp.float32),
        jnp.zeros((b, hq, s), jnp.float32),
        jnp.zeros((b, hq, s, dv), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (jnp.arange(nk), kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ----------------------------------------------------------- embedding_bag
def embedding_bag_ref(
    table: jnp.ndarray,    # [V, D]
    ids: jnp.ndarray,      # int32[B, L]
    weights: jnp.ndarray,  # f32[B, L]
    *,
    mode: str = "sum",
) -> jnp.ndarray:
    rows = jnp.take(table, ids, axis=0).astype(jnp.float32)   # [B, L, D]
    out = jnp.sum(rows * weights[:, :, None], axis=1)
    if mode == "mean":
        counts = jnp.sum((weights != 0.0).astype(jnp.float32), axis=1)
        out = out / jnp.maximum(counts, 1.0)[:, None]
    return out.astype(table.dtype)
