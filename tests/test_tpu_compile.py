"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is only
described (`v5e:2x2`), so these tests need no chip. They refuse what the
Pallas interpreter accepts: unsupported casts and reductions, misaligned
blocks, more VMEM or SMEM than a kernel may hold.

Shapes: Graph500 R-MAT scale 20 plus 64 planted 4-vertex needles
(n = 2^20 + 256), blocked at bn = 64, about 10.1 M nonzero blocks. W = 1 is
the LCC sweep (one packed word per vertex), W = 32 an NLCC wave of 1024
sources. The fused wave keeps its frontier in VMEM, so at that size its
eligibility gate routes it to the oracle, and the compiler refuses it too;
it compiles at the largest shape the gate admits.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports every test file.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.bitset_spmm import bitset_spmm
from repro.kernels.bitset_wave import BITSET_WAVE_MAX_BLOCKS, bitset_wave

BN = 64
N_PAD_20 = (1 << 20) + 4 * 64          # scale 20 + 64 needles, a bn multiple
NNZB_20 = 10_100_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described v5e chip, with the persistent compilation cache off
    (a compile for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dt, sharding=sharding) for shape, dt in specs]


def _compile_spmm(sharding, w, n_pad, nnzb):
    args = _shapes(sharding, ((nnzb, 2), jnp.int32),
                   ((nnzb, 1, BN * BN // 32), jnp.int32), ((n_pad, w), jnp.uint32))
    return jax.jit(lambda p, m, v: bitset_spmm(p, m, v, bn=BN, n_pad=n_pad)
                   ).lower(*args).compile()


def _compile_wave(sharding, w, n_pad, nnzb, hops):
    args = _shapes(sharding, ((nnzb, 2), jnp.int32),
                   ((nnzb, 1, BN * BN // 32), jnp.int32), ((n_pad, w), jnp.uint32),
                   ((hops, n_pad), jnp.uint32))
    return jax.jit(lambda p, m, v, c: bitset_wave(p, m, v, c, bn=BN, n_pad=n_pad)
                   ).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("w", [1, 32], ids=["lcc-W1", "nlcc-W32"])
def test_bitset_spmm_compiles_at_scale20(one_chip, w):
    compiled = _compile_spmm(one_chip, w, N_PAD_20, NNZB_20)
    assert _has_kernel(compiled)
    # the masks (4.8 GiB) are an argument; what the sweep adds must stay small
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def test_bitset_wave_at_scale20_is_gated_and_refused(one_chip):
    assert ops.bitset_wave_vmem_bytes(N_PAD_20, 32, BN) > ops.BITSET_WAVE_VMEM_BUDGET
    with pytest.raises(Exception, match="vmem"):
        _compile_wave(one_chip, 32, N_PAD_20, BITSET_WAVE_MAX_BLOCKS, hops=3)


def _largest_admitted_n_pad(w: int) -> int:
    n_pad = BN
    while ops.bitset_wave_vmem_bytes(n_pad + BN, w, BN) <= ops.BITSET_WAVE_VMEM_BUDGET:
        n_pad += BN
    return n_pad


@pytest.mark.parametrize("w,hops", [(32, 3), (1, 3), (32, 8)],
                         ids=["W32-L3", "W1-L3", "W32-L8"])
def test_bitset_wave_compiles_at_largest_admitted_shape(one_chip, w, hops):
    n_pad = _largest_admitted_n_pad(w)
    blocked = type("B", (), {"n_pad": n_pad, "bn": BN,
                             "nnzb": BITSET_WAVE_MAX_BLOCKS})()
    vals = jax.ShapeDtypeStruct((n_pad, w), np.uint32)
    assert ops._wave_eligible(vals, None, None, n_pad, None, None, blocked)
    compiled = _compile_wave(one_chip, w, n_pad, BITSET_WAVE_MAX_BLOCKS, hops)
    assert _has_kernel(compiled)
