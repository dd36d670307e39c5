"""JAX/Pallas API surface — the ONE choke point for the spellings this repo
uses (requirements.txt pins the JAX line).

Invariant (recorded in ROADMAP.md): every backend-specific or
version-sensitive JAX API is spelled here and nowhere else:

  - the TPU Pallas compiler params — pass ``dimension_semantics=`` and
    ``vmem_limit_bytes=`` to :func:`pallas_call`,
  - memory spaces and scratch constructors (:func:`vmem`,
    :func:`any_spec`, :func:`sync_copy`) and
    :func:`prefetch_scalar_grid_spec`,
  - mesh construction with typed axes (:func:`make_mesh`, :func:`axis_type`),
  - :func:`shard_map`,
  - the persistent compilation cache (:func:`enable_compile_cache`).

There is one spelling per API, the installed JAX's. A JAX that lacks one
fails loudly at the call; nothing here retries, drops an argument or falls
back. Kernel modules call :func:`pallas_call`; the dispatch registry
(``repro.kernels.registry``) decides compiled / interpret / reference per
call from eligibility alone, and a kernel that fails raises.
"""
from __future__ import annotations

import os
from typing import Any, Optional, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ------------------------------------------------------------------ pallas
def tpu_compiler_params(
    *,
    dimension_semantics: Optional[Sequence[str]] = None,
    vmem_limit_bytes: Optional[int] = None,
):
    """The TPU Pallas compiler-params object."""
    return pltpu.CompilerParams(
        dimension_semantics=(tuple(dimension_semantics)
                             if dimension_semantics is not None else None),
        vmem_limit_bytes=vmem_limit_bytes,
    )


def vmem(shape: Sequence[int], dtype) -> Any:
    """VMEM scratch-shape constructor."""
    return pltpu.VMEM(tuple(shape), dtype)


def any_spec() -> Any:
    """BlockSpec for an operand left where it is (HBM); the kernel moves it
    itself with :func:`sync_copy`."""
    return pl.BlockSpec(memory_space=pl.ANY)


def sync_copy(src_ref, dst_ref) -> None:
    """Blocking DMA between two refs (HBM <-> VMEM) inside a kernel."""
    pltpu.sync_copy(src_ref, dst_ref)


def prefetch_scalar_grid_spec(
    *,
    num_scalar_prefetch: int,
    grid: Sequence[int],
    in_specs: Sequence[Any],
    out_specs: Any,
    scratch_shapes: Sequence[Any] = (),
):
    """Scalar-prefetch grid spec (index maps may read the prefetched operands)."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=tuple(grid),
        in_specs=list(in_specs),
        out_specs=out_specs,
        scratch_shapes=list(scratch_shapes),
    )


def pallas_call(
    kernel,
    *,
    out_shape,
    grid: Optional[Sequence[int]] = None,
    grid_spec=None,
    in_specs=None,
    out_specs=None,
    scratch_shapes: Sequence[Any] = (),
    dimension_semantics: Optional[Sequence[str]] = None,
    vmem_limit_bytes: Optional[int] = None,
    interpret: bool = False,
    **extra,
):
    """`pl.pallas_call` with the TPU compiler params built from keywords.
    ``interpret=True`` runs the kernel in the Pallas interpreter (the
    off-TPU path the registry dispatches for ``force_pallas`` tests)."""
    kwargs = dict(out_shape=out_shape, interpret=interpret, **extra)
    if grid_spec is not None:
        kwargs["grid_spec"] = grid_spec
    else:
        if grid is not None:
            kwargs["grid"] = tuple(grid)
        if in_specs is not None:
            kwargs["in_specs"] = list(in_specs)
        if out_specs is not None:
            kwargs["out_specs"] = out_specs
        if scratch_shapes:
            kwargs["scratch_shapes"] = list(scratch_shapes)
    if dimension_semantics is not None or vmem_limit_bytes is not None:
        kwargs["compiler_params"] = tpu_compiler_params(
            dimension_semantics=dimension_semantics,
            vmem_limit_bytes=vmem_limit_bytes)
    return pl.pallas_call(kernel, **kwargs)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# -------------------------------------------------------------------- mesh
_AXIS_TYPES = {
    "auto": jax.sharding.AxisType.Auto,
    "explicit": jax.sharding.AxisType.Explicit,
    "manual": jax.sharding.AxisType.Manual,
}


def axis_type(kind: str = "auto"):
    """Resolve an axis-type name ("auto" | "explicit" | "manual") to its
    jax.sharding.AxisType member."""
    try:
        return _AXIS_TYPES[kind]
    except KeyError:
        raise ValueError(
            f"unknown axis type {kind!r}; expected one of {tuple(_AXIS_TYPES)}"
        ) from None


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    axis_types: Optional[Sequence[str]] = None,
    devices=None,
):
    """`jax.make_mesh` taking axis-type *names* ("auto"/"explicit"/"manual")."""
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    if axis_types is not None:
        kwargs["axis_types"] = tuple(axis_type(t) for t in axis_types)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


# --------------------------------------------------------------- shard_map
def shard_map(f, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None):
    """`jax.shard_map`; ``check_vma=None`` keeps JAX's default."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, **kwargs)


# ------------------------------------------------------- compilation cache
# The fixed fallback location: the cache key includes the path, so it must
# not move between runs (never a temporary name, a process id or the time).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already reads it and nothing is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``. Tests do not call this."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
