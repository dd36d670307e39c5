"""`bitset_spmm` — blocked bit-packed OR-SpMM, the LCC/NLCC hot loop on TPU.

Computes, over a block-sparse boolean adjacency (see graph/blocked.py):

    out[v, w] = OR_{u : arc (u -> v) active} vals[u, w]        (uint32 words)

TPU mapping: each nonzero (dst_block, src_block) pair is one grid step.
The packed block mask (one row of BN*BN/32 words) and the packed source
values [BN, W] are unpacked to {0,1} bf16 planes and contracted on the MXU:

    acc[BN, 32W] (+)= unpack(mask)[BN, BN] @ unpack(vals)[BN, 32W]

`acc > 0` is the OR. The accumulator lives in VMEM scratch across the grid
steps of one dst row (grid is ordered by dst block; "arbitrary" semantics);
the packed result is written at the row's last step, and the output block is
written back when the grid moves to the next dst row. A scalar-prefetched
step table drives the BlockSpec index maps — all indirection is resolved by
the grid. The table lists only blocks with an active arc and is cut into
chunks that fit SMEM (`_live_block_steps`, `SPMM_CHUNK`).

Mosaic has no unsigned casts or reductions and no lane-splitting reshapes,
so the kernel body sees the words as int32 (bitcast outside the kernel) and
moves bits between word and plane layouts with small 0/1 matmuls over byte
planes (`_unpack_bits` / `_pack_bits`): every value on the MXU is an integer
below 256, exact in bf16 with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _unpack_bits(words: jnp.ndarray) -> jnp.ndarray:
    """int32[R, W] -> bf16[R, 32W] of {0, 1}: bit b of word w -> column 32w+b.

    Each byte plane is spread to its 8 columns by a 0/1 matmul (one nonzero
    term per output, so exact), then the column's bit is shifted out."""
    r, w = words.shape
    c = 32 * w
    if w == 1:  # one word per row: a lane broadcast, no matmul
        bits = (jnp.broadcast_to(words, (r, 32)) >> _iota((r, 32), 1)) & 1
        return bits.astype(jnp.float32).astype(jnp.bfloat16)
    col = _iota((w, c), 1)
    word_of_col = (col >> 5) == _iota((w, c), 0)
    rep = jnp.zeros((r, c), jnp.float32)
    for k in range(4):
        byte = ((words >> (8 * k)) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        spread = (word_of_col & (((col & 31) >> 3) == k)).astype(jnp.bfloat16)
        rep = rep + jnp.dot(byte, spread, preferred_element_type=jnp.float32)
    shift = _iota((r, c), 1) & 7
    bits = (rep.astype(jnp.int32) >> shift) & 1
    return bits.astype(jnp.float32).astype(jnp.bfloat16)


def _unpack_mask(row: jnp.ndarray, bn: int) -> jnp.ndarray:
    """One block's mask words int32[1, bn * bnw] -> bf16[bn, bn] of {0, 1}:
    M[i, j] = bit j % 32 of word i * bnw + j // 32.

    The row is broadcast down the bn sublanes with each row keeping its own
    bnw words, then every byte plane is spread to its 8 columns by a 0/1
    matmul, as in `_unpack_bits`."""
    r = row.shape[1]
    bnw = r // bn
    lg = bnw.bit_length() - 1                      # bn is a power of two
    c = _iota((bn, r), 1)
    mine = (c >> lg) == _iota((bn, r), 0)
    words = jnp.where(mine, jnp.broadcast_to(row, (bn, r)), 0)
    wc = _iota((r, bn), 0) & (bnw - 1)
    j = _iota((r, bn), 1)
    rep = jnp.zeros((bn, bn), jnp.float32)
    for k in range(4):
        byte = ((words >> (8 * k)) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        spread = ((wc == (j >> 5)) & (((j & 31) >> 3) == k)).astype(jnp.bfloat16)
        rep = rep + jnp.dot(byte, spread, preferred_element_type=jnp.float32)
    bits = (rep.astype(jnp.int32) >> (_iota((bn, bn), 1) & 7)) & 1
    return bits.astype(jnp.float32).astype(jnp.bfloat16)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """bool[R, 32W] -> int32[R, W], the inverse of `_unpack_bits`.

    Per byte plane, each set bit contributes 2^(col % 8) and a 0/1 matmul
    sums the 8 columns of each word's byte (at most 255, exact)."""
    r, c = bits.shape
    w = c // 32
    col = _iota((r, c), 1)
    if w == 1:  # one word per row: a lane sum of disjoint bits
        return jnp.sum(jnp.where(bits, 1 << col, 0), axis=1, keepdims=True)
    weight = (1 << (col & 7)).astype(jnp.float32)
    gcol = _iota((c, w), 0)
    gather = ((gcol >> 5) == _iota((c, w), 1)).astype(jnp.bfloat16)
    out = jnp.zeros((r, w), jnp.int32)
    for k in range(4):
        part = jnp.where(bits & (((col & 31) >> 3) == k), weight, 0.0)
        byte = jnp.dot(part.astype(jnp.bfloat16), gather,
                       preferred_element_type=jnp.float32)
        out = out | (byte.astype(jnp.int32) << (8 * k))
    return out


def _row_bounds(dst_ref, b, nb):
    """(first, last): grid step b opens / closes its dst row."""
    db = dst_ref[b]
    first = jnp.logical_or(b == 0, dst_ref[jnp.maximum(b, 1) - 1] != db)
    last = jnp.logical_or(b == nb - 1, dst_ref[jnp.minimum(b + 1, nb - 1)] != db)
    return first, last


def _accumulate(acc_ref, mask_row, src_words, first):
    bn = acc_ref.shape[0]
    partial = jnp.dot(_unpack_mask(mask_row, bn), _unpack_bits(src_words),
                      preferred_element_type=jnp.float32)   # [BN, 32W]

    @pl.when(first)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += partial


def _kernel(dst_ref, src_ref, blk_ref, mask_ref, vals_ref, prev_ref, out_ref,
            acc_ref):
    b = pl.program_id(0)
    first, last = _row_bounds(dst_ref, b, pl.num_programs(0))
    _accumulate(acc_ref, mask_ref[0], vals_ref[...], first)

    @pl.when(last)
    def _emit():
        # OR with what earlier chunks wrote to this row (zeros at the start)
        out_ref[...] = _pack_bits(acc_ref[...] > 0.5) | prev_ref[...]


# Grid steps per pallas_call. The step table (dst, src, block index per step)
# is scalar-prefetched into SMEM, 12 B a step, so one call cannot span a
# large graph's blocks; the wrapper loops over chunks of this many steps.
SPMM_CHUNK = 16384


def _spmm_chunk(dst, src, blk, masks, vals, out, *, bn, interpret):
    w = vals.shape[1]
    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=3,
        grid=(dst.shape[0],),
        in_specs=[
            pl.BlockSpec((1, 1, bn * bn // 32), lambda b, d, s, k: (k[b], 0, 0)),
            pl.BlockSpec((bn, w), lambda b, d, s, k: (s[b], 0)),
            pl.BlockSpec((bn, w), lambda b, d, s, k: (d[b], 0)),
        ],
        out_specs=pl.BlockSpec((bn, w), lambda b, d, s, k: (d[b], 0)),
        scratch_shapes=[compat.vmem((bn, 32 * w), jnp.float32)],
    )
    return compat.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out.shape, jnp.int32),
        interpret=interpret,
        dimension_semantics=("arbitrary",),
        input_output_aliases={5: 0},
    )(dst, src, blk, masks, vals, out)


def _live_block_steps(pairs: jnp.ndarray, masks: jnp.ndarray, n_blocks: int,
                     chunk: int):
    """The step table of one sweep: the blocks whose mask has a set bit, in
    dst-sorted order, padded to whole chunks with steps on a dummy dst row
    (block row `n_blocks`). Returns (dst, src, blk, n_chunks_to_run)."""
    nnzb = masks.shape[0]
    n_steps = -(-nnzb // chunk) * chunk
    live = jnp.any(masks != 0, axis=(1, 2))
    (order,) = jnp.nonzero(live, size=n_steps, fill_value=nnzb)
    ext = jnp.concatenate(
        [pairs.astype(jnp.int32), jnp.asarray([[n_blocks, 0]], jnp.int32)])
    n_live = jnp.sum(live, dtype=jnp.int32)
    return (ext[order, 0], ext[order, 1], jnp.minimum(order, nnzb - 1),
            (n_live + chunk - 1) // chunk)


@functools.partial(jax.jit, static_argnames=("bn", "n_pad", "interpret", "chunk"))
def bitset_spmm(
    pairs: jnp.ndarray,    # int32[nnzb, 2] (dst_block, src_block), dst-sorted
    masks: jnp.ndarray,    # int32[nnzb, 1, BN*BN//32] dynamic active bitmasks
    vals: jnp.ndarray,     # uint32[n_pad, W] packed per-vertex values
    *,
    bn: int,
    n_pad: int,
    interpret: bool = False,
    chunk: int = SPMM_CHUNK,
) -> jnp.ndarray:
    """OR-aggregate packed words along active arcs; returns uint32[n_pad, W].

    Only blocks with an active arc are visited (edge elimination clears
    whole blocks as the prune proceeds); rows no visited block reaches stay
    zero. The sweep runs as a device loop of `chunk`-step kernel calls, each
    OR-ing into the previous call's output in place."""
    nnzb = masks.shape[0]
    w = vals.shape[1]
    out = jnp.zeros((n_pad + bn, w), jnp.int32)  # + one dummy block row
    if nnzb:
        chunk = min(chunk, nnzb)
        dst, src, blk, n_chunks = _live_block_steps(
            pairs, masks, n_pad // bn, chunk)
        vals_i = _as_i32(vals)

        def body(c, acc):
            take = lambda a: jax.lax.dynamic_slice(a, (c * chunk,), (chunk,))
            return _spmm_chunk(take(dst), take(src), take(blk), masks,
                               vals_i, acc, bn=bn, interpret=interpret)

        out = jax.lax.fori_loop(0, n_chunks, body, out)
    return jax.lax.bitcast_convert_type(out[:n_pad], jnp.uint32)


def _as_i32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x, jnp.int32)
