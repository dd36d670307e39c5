"""Block-sparse bitmask adjacency — the TPU-native layout for the LCC/NLCC
edge sweep (`bitset_spmm` kernel).

The paper's hot loop is "for every active arc (u -> v): omega-words of u are
OR-ed into an aggregate at v". On TPU we reformulate the dst-sorted arc sweep
as a *block-sparse boolean matmul*:

  - vertices are grouped in blocks of BN,
  - only nonempty (dst_block, src_block) adjacency blocks are materialized,
    each as a packed bitmask uint32[BN, BN/32] (bit j of row i = arc
    (src_block*BN + j) -> (dst_block*BN + i)),
  - the OR-aggregation  out[v] |= vals[u]  becomes, per block,
    unpack(mask) @ unpack(vals) > 0 on the MXU,
  - *edge elimination* clears bits in the dynamic mask; cleared bits
    contribute the OR identity — exactly the paper's "no messages are sent
    over eliminated edges".

The static structure (block list, per-arc bit coordinates) is host-built once
per graph; the dynamic bitmasks are recomputed on device from the per-arc
active vector with one segment_sum (bits are disjoint, so sum == OR).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class BlockedStructure:
    """Static (per-graph) block structure. Host numpy; small relative to edges."""

    n: int  # original vertex count
    bn: int  # block size (vertices per block)
    n_pad: int  # padded vertex count = n_blocks_v * bn
    pairs: np.ndarray  # int32[nnzb, 2] (dst_block, src_block), sorted
    edge_block: np.ndarray  # int32[m] block index of each arc (dst-sorted arc order)
    edge_word: np.ndarray  # int32[m] flat word index within the mask tensor
    edge_bit: np.ndarray  # uint32[m] bit value (1 << (src % 32))
    row_first: np.ndarray  # bool[nnzb] first block of its dst row
    row_last: np.ndarray  # bool[nnzb] last block of its dst row

    @property
    def nnzb(self) -> int:
        return int(self.pairs.shape[0])

    @property
    def bnw(self) -> int:
        return self.bn // 32

    @property
    def words_per_block(self) -> int:
        return self.bn * self.bnw

    @functools.cached_property
    def device_arrays(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(pairs, edge_word, edge_bit) as int32 device arrays, uploaded on
        first use and kept: every sweep and hop needs them, and at R-MAT
        scale 20 they are 0.6 GB of host arrays. The first use may come
        inside a traced program, so they are made eagerly (never a tracer
        kept past its trace)."""
        if self.nnzb * self.words_per_block >= 2**31:
            raise NotImplementedError(
                f"{self.nnzb} blocks of {self.words_per_block} words overflow "
                "the int32 word index; use a smaller bn")
        with jax.ensure_compile_time_eval():
            return (jnp.asarray(self.pairs, jnp.int32),
                    jnp.asarray(self.edge_word, jnp.int32),
                    jnp.asarray(self.edge_bit.view(np.int32)))


def build_blocked_structure(src: np.ndarray, dst: np.ndarray, n: int, bn: int = 256) -> BlockedStructure:
    """Build from dst-sorted arcs. bn must be a power of two >= 32 (a whole
    number of lane words per block row)."""
    if bn < 32 or bn & (bn - 1):
        raise ValueError(f"block size bn={bn} must be a power of two >= 32")
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    n_blocks_v = max((n + bn - 1) // bn, 1)
    n_pad = n_blocks_v * bn
    db, sb = dst // bn, src // bn
    key = db * n_blocks_v + sb
    order = np.argsort(key, kind="stable")
    uk, first_idx = np.unique(key[order], return_index=True)
    pairs = np.stack([uk // n_blocks_v, uk % n_blocks_v], axis=1).astype(np.int32)
    # per-arc block index (in the original dst-sorted arc order)
    edge_block = np.searchsorted(uk, key).astype(np.int32)
    bnw = bn // 32
    row = (dst % bn).astype(np.int64)
    col = (src % bn).astype(np.int64)
    edge_word = (edge_block.astype(np.int64) * (bn * bnw) + row * bnw + col // 32).astype(np.int64)
    edge_bit = (np.uint32(1) << (col % 32).astype(np.uint32)).astype(np.uint32)
    row_first = np.ones(len(uk), dtype=bool)
    row_first[1:] = pairs[1:, 0] != pairs[:-1, 0]
    row_last = np.ones(len(uk), dtype=bool)
    row_last[:-1] = pairs[1:, 0] != pairs[:-1, 0]
    return BlockedStructure(
        n=n, bn=bn, n_pad=n_pad, pairs=pairs,
        edge_block=edge_block, edge_word=edge_word, edge_bit=edge_bit,
        row_first=row_first, row_last=row_last,
    )


def masks_from_active(bs: BlockedStructure, edge_active: jnp.ndarray) -> jnp.ndarray:
    """Dynamic block bitmasks int32[nnzb, 1, bn * bnw] from the per-arc
    active vector (dst-sorted order): block b's word `row * bnw + col // 32`
    holds bit `col % 32`. One row of words per block keeps the TPU layout
    dense (a trailing [bn, bnw] pair would pad bnw to 128 lanes), and int32
    is the kernels' own word type, so no converted copy is made. Bits are
    disjoint per word, so segment-sum of the selected bit values equals the
    bitwise OR (bit 31 wraps to the sign bit, as it should)."""
    _, edge_word, edge_bit = bs.device_arrays
    bits = jnp.where(edge_active, edge_bit, jnp.int32(0))
    flat = jax.ops.segment_sum(
        bits, edge_word, num_segments=bs.nnzb * bs.words_per_block)
    return flat.reshape(bs.nnzb, 1, bs.words_per_block)


def pad_values(vals: jnp.ndarray, bs: BlockedStructure) -> jnp.ndarray:
    """Pad packed value rows [n, W] -> [n_pad, W]."""
    if vals.shape[0] == bs.n_pad:
        return vals
    pad = bs.n_pad - vals.shape[0]
    return jnp.concatenate([vals, jnp.zeros((pad,) + vals.shape[1:], vals.dtype)], axis=0)
