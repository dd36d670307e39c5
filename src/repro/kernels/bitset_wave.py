"""`bitset_wave` — fused multi-hop bit-packed OR-SpMM, the NLCC wave on TPU.

The NLCC token-passing wave (paper Alg. 5/6) is L repetitions of the same
blocked OR-SpMM as `bitset_spmm`, each followed by a per-hop candidacy mask:

    F_r = (OR_{arc (u -> v) active} F_{r-1}[u]) & cand[r]        r = 1..L

The single-hop route launches one `bitset_spmm` per hop, so every hop pays
kernel-boundary traffic around the frontier. Here the whole wave runs inside
ONE `pallas_call` with the packed frontier resident in VMEM across all hops:

  grid = (L, nnzb) — hops major, dst-sorted adjacency blocks minor.
  Two VMEM frontier planes ping-pong: an even hop reads the even plane and
  writes the odd one, an odd hop the reverse. Per (h, b) step the
  (dst_block, src_block) bitmask is unpacked and contracted against the src
  block's rows on the MXU, exactly like `bitset_spmm`; at the dst row's last
  block the row is written as pack(acc > 0) & cand[h]. The initial frontier
  is copied from HBM into the even plane at the first step, and the final
  plane is copied back to HBM at the last step — the only frontier traffic
  between hops is VMEM -> VMEM.

Rows of dst blocks that no pair visits are never written, so both planes are
zeroed before they first receive a hop (odd at hop 0, even at hop 1);
every visited row is rewritten in full at every hop.

Pack/unpack of the bool frontier happens ONCE per wave (in the caller), not
once per hop, and the block bitmasks are shared across hops (edge_active is
constant within a wave). `kernels/ops.py` counts the VMEM this kernel holds
(`bitset_wave_vmem_bytes`) and routes shapes past the budget to the oracle;
the same budget is passed to the compiler as the kernel's VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.bitset_spmm import _accumulate, _as_i32, _pack_bits, _row_bounds


def _zero(plane_ref, bn: int):
    zeros = jnp.zeros((bn, plane_ref.shape[1]), plane_ref.dtype)

    def body(i, carry):
        plane_ref[pl.ds(pl.multiple_of(i * bn, bn), bn), :] = zeros
        return carry

    jax.lax.fori_loop(0, plane_ref.shape[0] // bn, body, 0)


def _kernel(dst_ref, src_ref, mask_ref, cand_ref, vals_hbm, out_hbm, even_ref,
            odd_ref, acc_ref, *, n_hops: int):
    h = pl.program_id(0)
    b = pl.program_id(1)
    bn = acc_ref.shape[0]

    @pl.when(jnp.logical_and(h == 0, b == 0))
    def _load_initial():
        compat.sync_copy(vals_hbm, even_ref)
        _zero(odd_ref, bn)

    @pl.when(jnp.logical_and(h == 1, b == 0))
    def _clear_hop0_plane():
        _zero(even_ref, bn)

    # hop h reads the even plane when h is even and writes the other one
    even_hop = h % 2 == 0
    db = dst_ref[b]
    sb = src_ref[b]
    first, last = _row_bounds(dst_ref, b, pl.num_programs(1))
    src = pl.ds(pl.multiple_of(sb * bn, bn), bn)
    src_rows = jnp.where(even_hop, even_ref[src, :], odd_ref[src, :])
    _accumulate(acc_ref, mask_ref[0], src_rows, first)

    @pl.when(last)
    def _emit():
        row = pl.ds(pl.multiple_of(db * bn, bn), bn)
        packed = _pack_bits(acc_ref[...] > 0.5)
        packed = jnp.where(cand_ref[0] != 0, packed, 0)

        @pl.when(even_hop)
        def _to_odd():
            odd_ref[row, :] = packed

        @pl.when(jnp.logical_not(even_hop))
        def _to_even():
            even_ref[row, :] = packed

    @pl.when(jnp.logical_and(h == n_hops - 1, b == pl.num_programs(1) - 1))
    def _store_final():
        compat.sync_copy(odd_ref if n_hops % 2 else even_ref, out_hbm)


# VMEM the kernel may hold; `ops.bitset_wave_vmem_bytes` counts what a shape
# needs and the eligibility gate admits only shapes within this budget.
BITSET_WAVE_VMEM_BUDGET = 12 * 2**20
# The (dst, src) step table of all blocks is scalar-prefetched into SMEM
# (8 B a block) for the whole wave; the gate admits graphs up to this many.
BITSET_WAVE_MAX_BLOCKS = 32768


@functools.partial(jax.jit, static_argnames=("bn", "n_pad", "interpret"))
def bitset_wave(
    pairs: jnp.ndarray,   # int32[nnzb, 2] (dst_block, src_block), dst-sorted
    masks: jnp.ndarray,   # int32[nnzb, 1, BN*BN//32] dynamic active bitmasks
    vals: jnp.ndarray,    # uint32[n_pad, W] packed initial frontier (hop 0)
    cand: jnp.ndarray,    # uint32[L, n_pad] per-hop candidacy, 0 / 0xFFFFFFFF
    *,
    bn: int,
    n_pad: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Run the full L-hop wave; returns the hop-L frontier uint32[n_pad, W]."""
    nnzb = masks.shape[0]
    n_hops = cand.shape[0]
    w = vals.shape[1]
    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=2,
        grid=(n_hops, nnzb),
        in_specs=[
            pl.BlockSpec((1, 1, bn * bn // 32), lambda h, b, d, s: (b, 0, 0)),
            # one candidacy column per dst block row: [L, n_pad, 1] keeps the
            # vertex axis on sublanes, where the packed rows live
            pl.BlockSpec((1, bn, 1), lambda h, b, d, s: (h, d[b], 0)),
            compat.any_spec(),
        ],
        out_specs=compat.any_spec(),
        scratch_shapes=[
            compat.vmem((n_pad, w), jnp.int32),
            compat.vmem((n_pad, w), jnp.int32),
            compat.vmem((bn, 32 * w), jnp.float32),
        ],
    )
    out = compat.pallas_call(
        functools.partial(_kernel, n_hops=n_hops),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, w), jnp.int32),
        interpret=interpret,
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=BITSET_WAVE_VMEM_BUDGET,
    )(pairs[:, 0], pairs[:, 1], masks, _as_i32(cand)[:, :, None],
      _as_i32(vals))
    return jax.lax.bitcast_convert_type(out, jnp.uint32)
