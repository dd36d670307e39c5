"""Pallas TPU kernels for the performance-critical compute layers.

  bitset_spmm     — blocked bit-packed OR-SpMM: the LCC/NLCC edge sweep
  bitset_wave     — the fused multi-hop NLCC wave over the same blocks
  segment_agg     — fused 4-way GNN neighborhood aggregation (PNA bank)
  flash_attention — causal/GQA/sliding-window attention (LM hot loop)
  embedding_bag   — scalar-prefetch gather + VMEM bag reduce (recsys hot loop)

Dispatch contract
-----------------
Every kernel is declared in the registry (`repro.kernels.registry`) with
three parts: its Pallas entrypoint, its pure-jnp oracle from `ref.py`
(identical numerics contract — parity tests enforce allclose), and a
shape-eligibility predicate. Public callers go through the jit'd wrappers in
`repro.kernels.ops`; per call, `registry.dispatch()` picks exactly one of:

  pallas-compiled    eligible call on a TPU backend
  pallas-interpret   eligible call with force_pallas=True off-TPU (tests)
  reference oracle   ineligible shapes, or off-TPU without force_pallas

A Pallas call that fails raises; nothing falls back to the oracle behind the
caller's back. `registry.count_dispatches()` shows which mode each kernel ran
in.

Compat invariant
----------------
No module outside `repro.kernels.compat` may touch backend-specific JAX API
surface: the TPU compiler params, memory spaces, the mesh axis-type enum,
mesh-construction kwargs, or shard_map. Kernels use `compat.pallas_call` /
`compat.vmem` / `compat.prefetch_scalar_grid_spec`; engine and launch code use
`compat.make_mesh` / `compat.shard_map`.
"""
from repro.kernels import compat, ops, ref, registry  # noqa: F401
